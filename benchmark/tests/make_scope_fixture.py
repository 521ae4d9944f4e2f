"""Writes ``scope_fixture.textproto``: a hand-made trace of a program that
names the parts of its step and writes its host spans, shaped as a real
one from the v5e is (PR 27): an ``XLA Ops`` event is named by its HLO line
and its *metadata* carries the ``op_name`` path as the stat ``tf_op``; the
host's ``python3`` thread has the program's ``pt.*`` spans beside the
harness's ``bench.*``.

Five runs of the step module, 100 us each, back to back from t = 1000 us.
In every run, in microseconds from its start:

    [ 0,  4)  embed        jvp(embed)/gather
    [ 4, 14)  self_attn    jvp(self_attn)/dot_general         (projection)
    [14, 26)  attention    jvp(self_attn)/attention/dot_general   (nested)
    [26, 46)  mlp          jvp(mlp)/dot_general
    [46, 54)  head_loss    transpose(jvp(head_loss))/dot_general  (new spelling)
    [54, 56)  head_loss    transpose(jvp(f))/head_loss/reduce_sum (old spelling)
    [56, 62)  attention    transpose(jvp(f))/self_attn/attention/mul
    [62, 64)  unscoped     jit(dot_product_attention)/mul  (a function's name)
    [63, 65)  unscoped     a copy with no ``tf_op`` at all (overlaps by 1 us)
    [65, 70)  nothing: the device waits
    [70,100)  optimizer    optimizer/add

A step: attention 18, trunk 4 + 10 + 20 = 34, head_loss 10, optimizer 30,
unscoped 4: 96 us of operations, 95 us busy (the overlap). The host, in
every run: ``bench.dispatch`` [59, 81), in it ``pt.step`` [60, 80), in it
``pt.h2d`` [61, 63) and ``pt.compute`` [64, 78): the gap lies under
``pt.compute``. Whole steps are runs 2 to 4.

    python3 benchmark/tests/make_scope_fixture.py
"""
import os

US = 1_000_000  # picoseconds
P = "jit(train_step)/"
OPS = {  # metadata id: (HLO line, tf_op or None)
    2: ("%gather.1 = bf16[8,64]{1,0} gather(bf16[512,64]{1,0} %p.0)",
        P + "jvp(embed)/gather:"),
    3: ("%fusion.1 = bf16[8,192]{1,0} fusion(bf16[8,64]{1,0} %p.1), kind=kOutput, calls=%fused_computation.1",
        P + "jvp(self_attn)/dot_general:"),
    4: ("%fusion.2 = bf16[8,8]{1,0} fusion(bf16[8,64]{1,0} %p.2), kind=kOutput, calls=%fused_computation.2",
        P + "jvp(self_attn)/attention/dot_general:"),
    5: ("%fusion.3 = bf16[8,256]{1,0} fusion(bf16[8,64]{1,0} %p.3), kind=kOutput, calls=%fused_computation.3",
        P + "jvp(mlp)/dot_general:"),
    6: ("%fusion.4 = bf16[512,64]{1,0} fusion(bf16[8,512]{1,0} %p.4), kind=kOutput, calls=%fused_computation.4",
        P + "transpose(jvp(head_loss))/dot_general:"),
    7: ("%fusion.5 = f32[]{:T(128)} fusion(f32[8]{0} %p.5), kind=kLoop, calls=%fused_computation.5",
        P + "transpose(jvp(f))/head_loss/reduce_sum:"),
    8: ("%fusion.6 = bf16[8,8]{1,0} fusion(bf16[8,8]{1,0} %p.6), kind=kLoop, calls=%fused_computation.6",
        P + "transpose(jvp(f))/self_attn/attention/mul:"),
    9: ("%fusion.7 = bf16[8,64]{1,0} fusion(bf16[8,64]{1,0} %p.7), kind=kLoop, calls=%fused_computation.7",
        P + "jit(dot_product_attention)/mul:"),
    10: ("%copy.1 = f32[8]{0} copy(f32[8]{0} %p.8)", None),
    11: ("%fusion.8 = (bf16[64,64]{1,0}, f32[64,64]{1,0}) fusion(f32[64,64]{1,0} %p.9), kind=kLoop, calls=%fused_computation.8",
         P + "optimizer/add:"),
}
PLACE = {2: (0, 4), 3: (4, 10), 4: (14, 12), 5: (26, 20), 6: (46, 8),
         7: (54, 2), 8: (56, 6), 9: (62, 2), 10: (63, 2), 11: (70, 30)}
NAMES = {1: "jit_train_step(7)", 20: "bench.dispatch", 21: "pt.step",
         22: "pt.h2d", 23: "pt.compute", 24: "7"}
HOST = {20: (59, 22), 21: (60, 20), 22: (61, 2), 23: (64, 14)}
TF_OP = 1  # the stat metadata's id


def event(meta, start_us, length_us):
    return (f"    events {{ metadata_id: {meta} offset_ps: "
            f"{int(start_us * US)} duration_ps: {int(length_us * US)} }}\n")


def line(ident, name, events):
    return (f'  lines {{ id: {ident} name: "{name}" timestamp_ns: 1000000\n'
            + "".join(events) + "  }\n")


def main():
    runs = [100.0 * i for i in range(5)]
    metadata = "".join(
        f'  event_metadata {{ key: {k} value {{ id: {k} name: "{v}" }} }}\n'
        for k, v in NAMES.items())
    for k, (hlo, path) in OPS.items():
        stat = (f' stats {{ metadata_id: {TF_OP} str_value: "{path}" }}'
                if path else "")
        metadata += (f'  event_metadata {{ key: {k} value {{ id: {k} '
                     f'name: "{hlo}"{stat} }} }}\n')
    metadata += (f'  stat_metadata {{ key: {TF_OP} value {{ id: {TF_OP} '
                 f'name: "tf_op" }} }}\n')
    device = (
        line(1, "Steps", [event(24, t, 100) for t in runs])
        + line(2, "XLA Modules", [event(1, t, 100) for t in runs])
        + line(3, "XLA Ops", [event(k, t + a, n) for t in runs
                              for k, (a, n) in PLACE.items()]))
    host = line(1, "python3", [event(k, t + a, n) for t in runs
                               for k, (a, n) in HOST.items()])
    text = ('planes { id: 1 name: "/device:TPU:0"\n' + metadata + device
            + '}\nplanes { id: 2 name: "/host:CPU"\n' + metadata + host + "}\n")
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "scope_fixture.textproto")
    with open(path, "w") as f:
        f.write(text)


if __name__ == "__main__":
    main()
