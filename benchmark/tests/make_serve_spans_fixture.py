"""Writes ``serve_spans_fixture.textproto``: a hand-made trace of a serving
engine, with the planes and lines of a real one from the v5e
(``/device:TPU:0`` with ``XLA Modules`` and ``XLA Ops``, ``/host:CPU``
with the scheduler's thread and its ``pt.serve.*`` spans), small enough to
check by eye.

Six iterations of 100 us, iteration i from t = 100 + 100 i. The device
runs one decode step over [t + 20, t + 70): two operations, over
[t + 20, t + 40) and [t + 45, t + 70), so 5 us of every run are idle
inside it. The host, on one thread:

    serve.iter          [t - 9, t + 90)
      serve.admit       [t - 9, t - 2)
      serve.decode_round [t - 2, t + 85)   (no phase over [t - 2, t - 1))
        serve.blocks    [t - 1, t + 5)
        serve.arrays    [t + 5, t + 15)
        serve.dispatch  [t + 15, t + 20)
        serve.fetch     [t + 20, t + 75)
        serve.tokens    [t + 75, t + 85)
      serve.retire      [t + 85, t + 90)
    (no span over [t + 90, t + 91))

So the 50 us the device idles between two runs, [t + 70, t + 120), span
nine pieces: fetch 5, tokens 10, retire 5, no span 1, admit 7, a round
with no phase 1, blocks 6, arrays 10, dispatch 5. Whole runs are 2 to 5:
window [220, 570), 350 us; busy 4 x 45 = 180 us; idle 170 us: three gaps
of 50 and four times 5 in step.

    python3 benchmark/tests/make_serve_spans_fixture.py
"""
import os

US = 1_000_000  # picoseconds
NAMES = {
    1: "jit_serve_decode_b64(7)",
    2: "%fusion.1 = bf16[64,1024]{1,0:T(8,128)(2,1)} fusion(bf16[64,1024]{1,0} %p.0), kind=kOutput, calls=%fused_computation.1",
    3: "%fusion.2 = f32[64,8]{1,0:T(8,128)} fusion(f32[64,8]{1,0} %p.1), kind=kLoop, calls=%fused_computation.2",
    10: "pt.serve.iter", 11: "pt.serve.admit", 12: "pt.serve.decode_round",
    13: "pt.serve.blocks", 14: "pt.serve.arrays", 15: "pt.serve.dispatch",
    16: "pt.serve.fetch", 17: "pt.serve.tokens", 18: "pt.serve.retire",
}
# (metadata id, start, end) from the iteration's t, in us
HOST = ((10, -9, 90), (11, -9, -2), (12, -2, 85), (13, -1, 5), (14, 5, 15),
        (15, 15, 20), (16, 20, 75), (17, 75, 85), (18, 85, 90))


def event(meta, start_us, length_us):
    return (f"    events {{ metadata_id: {meta} offset_ps: "
            f"{int(start_us * US)} duration_ps: {int(length_us * US)} }}\n")


def line(ident, name, events):
    return (f'  lines {{ id: {ident} name: "{name}" timestamp_ns: 1000000\n'
            + "".join(events) + "  }\n")


def main():
    starts = [100.0 + 100.0 * i for i in range(6)]
    metadata = "".join(
        f'  event_metadata {{ key: {k} value {{ id: {k} name: "{v}" }} }}\n'
        for k, v in NAMES.items())
    device = (
        line(1, "XLA Modules", [event(1, t + 20, 50) for t in starts])
        + line(2, "XLA Ops", [e for t in starts for e in (
            event(2, t + 20, 20), event(3, t + 45, 25))]))
    host = line(1, "DecodeScheduler", [
        event(meta, t + a, b - a) for t in starts for meta, a, b in HOST])
    text = ('planes { id: 1 name: "/device:TPU:0"\n' + metadata + device
            + '}\nplanes { id: 2 name: "/host:CPU"\n' + metadata + host + "}\n")
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "serve_spans_fixture.textproto")
    with open(path, "w") as f:
        f.write(text)


if __name__ == "__main__":
    main()
