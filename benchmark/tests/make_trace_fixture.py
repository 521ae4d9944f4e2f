"""Writes ``trace_fixture.textproto``: a hand-made trace with the planes
and lines of a real one from the v5e (PR 26: ``/device:TPU:0`` with
``Steps``, ``XLA Modules``, ``XLA Ops``, ``Async XLA Ops``; ``/host:CPU``
with the ``python3`` thread), small enough to check by eye.

Five runs of the step module, 100 us each, back to back from t = 1000 us.
In every run: a matmul fusion over [0, 40), a loop fusion over [40, 70), a
second loop fusion over [45, 60) inside it (overlap: counted once), nothing
over [70, 80), a fusion of another layer with the same signature as the
first over [80, 100). An async copy spans the whole run on its own line
(not an operation's busy time). The host dispatches during [68, 82) of
every run. Whole steps are runs 2 to 4: window 300 us, busy 270 us.

    python3 benchmark/tests/make_trace_fixture.py
"""
import os

US = 1_000_000  # picoseconds
NAMES = {
    1: "jit_step_core(123)",
    2: "%fusion.1 = bf16[8,64]{1,0:T(8,128)(2,1)} fusion(bf16[8,64]{1,0} %p.0), kind=kOutput, calls=%fused_computation.1",
    3: "%fusion.2 = f32[64]{0:T(64)} fusion(f32[64]{0} %p.1), kind=kLoop, calls=%fused_computation.2",
    4: "%fusion.3 = f32[8]{0:T(8)} fusion(f32[8]{0} %p.2), kind=kLoop, calls=%fused_computation.3",
    5: "%fusion.9 = bf16[8,64]{1,0:T(8,128)(2,1)} fusion(bf16[8,64]{1,0} %p.3), kind=kOutput, calls=%fused_computation.9",
    6: "%copy-start.1 = (f32[8]{0}, f32[8]{0}, u32[]) copy-start(f32[8]{0} %p.4)",
    7: "bench.dispatch", 8: "bench.fetch_loss", 9: "bench.next_batch",
    10: "step",
}


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
    device = (
        line(1, "Steps", [event(10, t, 100) for t in runs])
        + line(2, "XLA Modules", [event(1, t, 100) for t in runs])
        + line(3, "XLA Ops", [e for t in runs for e in (
            event(2, t, 40), event(3, t + 40, 30), event(4, t + 45, 15),
            event(5, t + 80, 20))])
        + line(4, "Async XLA Ops", [event(6, t, 100) for t in runs]))
    host = line(1, "python3", [e for t in runs for e in (
        event(9, t + 66, 2), event(7, t + 68, 14), event(8, t + 82, 84))])
    text = ('planes { id: 1 name: "/device:TPU:0"\n' + metadata + device
            + '}\nplanes { id: 2 name: "/host:CPU"\n' + metadata + host + "}\n")
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "trace_fixture.textproto")
    with open(path, "w") as f:
        f.write(text)


if __name__ == "__main__":
    main()
