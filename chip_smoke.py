"""chip_smoke.py — the quickest proof that the system still starts on the chip.

Drives the two main paths once, at the full width of GPT-2 345M, through
the entry points a user calls (``bench.build_trainer`` →
``fleet.ParallelTrainStep``; ``inference.serving.TokenServingEngine``):

- **train-1chip**  one compile plus a few steps on one fixed batch;
- **train-4chip**  the same trainer over a (dp, mp, sharding) = (2, 1, 2)
  mesh with ZeRO-2, when the process sees four chips: losses must track
  the one-chip run, and the state must really be spread over the chips;
- **serve**        four requests of mixed prompt length through the token
  server, and paged-prefill logits against the dense forward.

Weights are random from a seed, token ids synthetic: no network, no
dataset, no ``.git``. Everything runs in this one process, phases in
sequence with engines freed in between — a chip belongs to one process.

Output: a first JSON line naming the device and the installation, one
JSON line per phase, and as the LAST line of stdout
``{"ok": true, "device": {...}}``. Times in the phase lines are smoke
readings (a handful of steps, first-touch effects included), not
benchmark numbers. Any failed check or exception ends the run with a
traceback and a non-zero exit code; without a TPU it refuses to start.

The phase functions take the model configuration as an argument:
``tests/test_chip_smoke.py`` drives the same checks at a tiny width on
the CPU mesh.
"""
from __future__ import annotations

import collections
import gc
import importlib.metadata
import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np

# logits of the same f32 weights through two differently-ordered programs:
# on the TPU every f32 matmul runs as bf16 passes, so two orderings agree to
# about 1e-2 of the logit scale (measured on the v5e: see CHANGES.md PR 21),
# not to the 1e-4 the CPU gate (tools/check_decode.py) holds
CHIP_LOGIT_TOL = 5e-2


class CheckFailed(AssertionError):
    pass


def check(ok, what: str) -> None:
    if not ok:
        raise CheckFailed(what)


def emit(record: dict) -> None:
    print(json.dumps(record), flush=True)


def device_record() -> dict:
    dev = jax.devices()[0]
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(jax.devices())}


# -- what each phase reports about the shared machinery ----------------------

_CACHE_EVENTS = {"/jax/compilation_cache/cache_hits": "hits",
                 "/jax/compilation_cache/cache_misses": "misses"}
_cache_counts: collections.Counter = collections.Counter()


def _on_jax_event(event, **_):
    if event in _CACHE_EVENTS:
        _cache_counts[_CACHE_EVENTS[event]] += 1


def cache_state() -> dict:
    """The persistent compile cache now: its directory, the entries on
    disk, and the hits and misses JAX has counted in this process."""
    if not _cache_counts:  # first call: start counting (both keys present)
        _cache_counts.update(hits=0, misses=0)
        jax.monitoring.register_event_listener(_on_jax_event)
    d = jax.config.jax_compilation_cache_dir
    entries = (sum(n.endswith("-cache") for n in os.listdir(d))
               if d and os.path.isdir(d) else 0)
    return {"dir": d, "entries": entries, **_cache_counts}


def cache_report(before: dict) -> dict:
    now = cache_state()
    return {"dir": now["dir"], "entries_before": before["entries"],
            "entries_after": now["entries"],
            "hits": now["hits"] - before["hits"],
            "misses": now["misses"] - before["misses"]}


def tier_fallbacks() -> int:
    from paddle_tpu.profiler.telemetry import get_telemetry

    return get_telemetry().counter_value("attn/tier_fallbacks")


def tier_report(dense, paged_keys, fallbacks_before: int) -> dict:
    """The attention tiers a phase ran. ``dense``: the (L, head_dim,
    causal) of its dense calls, whose tier is a rule and is read back from
    ``gauge/attn/tier.*``. ``paged_keys`` (``tier_policy.make_paged_key``;
    empty where the paged policy does not measure): the verdicts behind its
    decode shapes, each with both tiers' times: on the TPU a tier the
    compiler refuses raises inside the micro-bench
    (``tier_policy.TierCompileError``), so a verdict that exists has a
    time for every tier that was offered — asserted here, not assumed."""
    from paddle_tpu.ops import tier_policy
    from paddle_tpu.profiler.telemetry import get_telemetry

    names = {i: name for name, i in tier_policy.TIER_IDS.items()}
    scalars = get_telemetry().scalars()
    tiers = {}
    for L, d, causal in dense:
        gauge = f"gauge/attn/tier.{tier_policy.gauge_key(L, d, causal)}"
        check(scalars.get(gauge) in names,
              f"{gauge} names no tier: {scalars.get(gauge)}")
        tiers[gauge] = names[scalars[gauge]]
    verdicts = {}
    for key in paged_keys:
        v = tier_policy.registry().verdict(key)
        check(v is not None, f"no attention-tier verdict for {key}")
        check(v.get("candidates")
              and set(v["timings_ms"]) == set(v["candidates"]),
              f"tier verdict {key} lacks a time for a candidate that was "
              f"offered: {v}")
        verdicts[key] = {f: v[f] for f in ("tier", "candidates",
                                           "timings_ms")}
    fallbacks = tier_fallbacks() - fallbacks_before
    check(fallbacks == 0, f"counter/attn/tier_fallbacks moved by "
                          f"{fallbacks}: a dispatch was rerouted")
    return {"tiers": tiers, "verdicts": verdicts,
            "tier_fallbacks": fallbacks}


def _memory(devices) -> dict:
    """Allocator readings per device (None where the backend has none)."""
    out = {}
    for d in devices:
        stats = d.memory_stats() or {}
        out[str(d.id)] = {k: stats.get(k) for k in
                          ("bytes_in_use", "peak_bytes_in_use")}
    return out


# -- train -------------------------------------------------------------------

def train_phase(name, config, devices, mesh_shape=None, zero_stage=0,
                batch=8, seq=1024, steps=6, reference_losses=None) -> dict:
    """One compile plus ``steps`` steps on one fixed batch. ``mesh_shape``
    None is the one-device ``("dp",)`` mesh of ``bench.py``; otherwise a
    ``("dp", "mp", "sharding")`` mesh over ``devices``, and the proof that
    the state is spread over them is part of the phase."""
    from jax.sharding import Mesh

    from bench import build_trainer, token_batch

    cache0, fallbacks0 = cache_state(), tier_fallbacks()
    if mesh_shape is None:
        mesh = Mesh(np.array(devices[:1]), ("dp",))
    else:
        n = int(np.prod(mesh_shape))
        mesh = Mesh(np.array(devices[:n]).reshape(mesh_shape),
                    ("dp", "mp", "sharding"))
    step = build_trainer(config, mesh, zero_stage=zero_stage)
    ids, labels = token_batch(config, batch, seq)

    t0 = time.perf_counter()
    losses = [float(step((ids, labels), (labels,)).numpy())]
    first_step_s = time.perf_counter() - t0
    step_ms = []
    for _ in range(steps - 1):
        t0 = time.perf_counter()
        losses.append(float(step((ids, labels), (labels,)).numpy()))
        step_ms.append(round((time.perf_counter() - t0) * 1e3, 2))

    check(all(np.isfinite(losses)), f"{name}: non-finite loss in {losses}")
    check(losses[-1] < losses[0],
          f"{name}: loss did not fall on a fixed batch: {losses}")
    compiles = step._jitted.tracker.compiles
    check(compiles == 1, f"{name}: {compiles} compiles for one signature")
    record = {
        "phase": name, "ok": True,
        "mesh": dict(mesh.shape), "zero_stage": zero_stage,
        "batch": batch, "seq": seq, "layers": config.num_layers,
        "hidden": config.hidden_size,
        # trace + XLA compile + the first step
        "compile_s": round(first_step_s, 2),
        "smoke_step_ms": step_ms,
        "losses": [round(v, 5) for v in losses],
        "compiles": compiles,
    }
    if reference_losses is not None:
        record["losses_vs_one_chip"] = _check_tracks(
            name, losses, reference_losses)
    if mesh_shape is not None:
        record["spread"] = _check_spread(name, step, mesh)
    record["memory"] = _memory(mesh.devices.flat)
    record["attention"] = tier_report(
        [(seq, config.hidden_size // config.num_heads, True)], [], fallbacks0)
    record["compile_cache"] = cache_report(cache0)
    emit(record)
    return record


def _check_tracks(name, losses, reference) -> list:
    """Per-step losses against the one-chip run, by the tolerance of
    ``__graft_entry__.dryrun_multichip``: bf16 compute and another
    reduction order are a few e-3 relative, and one bf16 ulp at the
    loss's magnitude must be admitted on top."""
    diffs = []
    for i, (got, ref) in enumerate(zip(losses, reference)):
        ulp = 2.0 ** (np.floor(np.log2(max(abs(ref), 1e-6))) - 7)
        tol = 5e-3 * max(abs(ref), 1.0) + ulp
        check(abs(got - ref) <= tol,
              f"{name}: step {i} loss {got} vs one-chip {ref} (tol {tol})")
        diffs.append(round(got - ref, 5))
    return diffs


def _check_spread(name, step, mesh) -> dict:
    """'All on the first chip' is the failure to look for: layers are
    created committed to device 0 and every other trainer in the tree
    builds a one-device mesh. Three independent views must agree that the
    state is spread: the arrays' own shards, the allocator of every
    device, and the collectives in the compiled program."""
    from paddle_tpu.profiler import collective_attrib

    devices = list(mesh.devices.flat)
    ways = mesh.shape["sharding"]
    # 1. the arrays' own shards
    held = collections.Counter()
    for leaf in jax.tree_util.tree_leaves((step._params, step._opt_state)):
        for shard in leaf.addressable_shards:
            held[shard.device] += shard.data.nbytes
    check(all(held[d] >= 0.5 * max(held.values()) for d in devices),
          f"{name}: train state is not spread over the mesh: "
          f"{ {str(d): held[d] for d in devices} }")
    opt_bytes = sharded_bytes = sharded_leaves = 0
    for leaf in jax.tree_util.tree_leaves(step._opt_state):
        opt_bytes += leaf.nbytes
        spec = tuple(leaf.sharding.spec) + (None,) * leaf.ndim
        if "sharding" not in spec[:leaf.ndim]:
            continue
        dim = spec.index("sharding")
        want = tuple(s // ways if i == dim else s
                     for i, s in enumerate(leaf.shape))
        shards = leaf.addressable_shards
        check(len({s.device for s in shards}) == len(devices)
              and all(s.data.shape == want for s in shards),
              f"{name}: optimizer-state leaf {leaf.shape} {spec} is not "
              f"held as 1/{ways} on each of {len(devices)} devices")
        sharded_bytes += leaf.nbytes
        sharded_leaves += 1
    check(sharded_bytes >= 0.9 * opt_bytes,
          f"{name}: only {sharded_bytes}/{opt_bytes} bytes of optimizer "
          f"state are sharded over 'sharding'")
    # 2. the allocator of every device (backends that report one)
    in_use = {d: (d.memory_stats() or {}).get("bytes_in_use")
              for d in devices}
    for d in devices:
        check(in_use[d] is None or in_use[d] >= held[d],
              f"{name}: device {d} reports {in_use[d]} bytes in use but "
              f"holds {held[d]} bytes of train state")
    # 3. the compiled program
    ops = collective_attrib.inventory(["fleet.train_step"]).get(
        "fleet.train_step", [])
    kinds = collections.Counter(
        f"{op.opcode.removesuffix('-start')}@{op.axis}" for op in ops)
    stems = {k.split("@")[0] for k in kinds}
    check(stems & {"all-reduce", "reduce-scatter"} and "all-gather" in stems,
          f"{name}: the compiled step lacks the gradient reduction or the "
          f"parameter all-gather: {dict(kinds)}")
    return {"state_bytes_per_device": {str(d.id): held[d] for d in devices},
            "opt_state_leaves_sharded": sharded_leaves,
            "opt_state_bytes_sharded_frac": round(sharded_bytes / opt_bytes,
                                                  4),
            "collectives": dict(kinds)}


# -- serve -------------------------------------------------------------------

def serve_phase(config, prompt_lens=(64, 128, 256, 512), new_tokens=32,
                decode_buckets=(1, 2, 4), prefill_chunk=128, block_size=16,
                logit_tol=CHIP_LOGIT_TOL) -> dict:
    """The checks of ``tools/check_decode.py``'s parity phase at the given
    width, without fault injection: every request ends ``ok`` with its
    full budget of tokens, no KV block leaks, no tier fallback, and the
    paged prefill's logits for the longest prompt agree with the dense
    eval-mode forward within ``logit_tol``."""
    import paddle_tpu
    from bench import build_model
    from paddle_tpu.inference.serving import (TokenServeConfig,
                                              TokenServingEngine,
                                              paged_prefill_logits)
    from paddle_tpu.ops import tier_policy

    cache0, fallbacks0 = cache_state(), tier_fallbacks()
    model = build_model(config)
    model.eval()
    rng = np.random.RandomState(11)
    prompts = [rng.randint(0, config.vocab_size, n).astype(np.int32)
               for n in prompt_lens]
    max_seq = -(-(max(prompt_lens) + new_tokens) // block_size) * block_size
    per_seq = max_seq // block_size
    engine = TokenServingEngine(model, TokenServeConfig(
        capacity=4 * len(prompts), decode_buckets=decode_buckets,
        prefill_chunk=prefill_chunk, kv_block_size=block_size,
        kv_blocks=len(prompts) * per_seq + 1, max_seq_len=max_seq))
    t0 = time.perf_counter()
    engine.start()  # compiles every decode bucket and the prefill chunk
    compile_s = time.perf_counter() - t0
    try:
        t0 = time.perf_counter()
        reqs = [engine.submit(p, max_new_tokens=new_tokens) for p in prompts]
        for r in reqs:
            r.wait(600)
        serve_s = time.perf_counter() - t0
    finally:
        engine.shutdown()
    for n, r in zip(prompt_lens, reqs):
        check(r.status == "ok" and len(r.outputs[0]) == new_tokens,
              f"serve: the {n}-token prompt ended {r.status!r} with "
              f"{len(r.outputs[0]) if r.outputs else 0} tokens: {r.detail}")
    kv = engine.kv_accounting()
    check(kv["leaked_blocks"] == 0, f"serve: leaked KV blocks: {kv}")

    longest = prompts[int(np.argmax(prompt_lens))]
    paged = paged_prefill_logits(model, longest, chunk=prefill_chunk,
                                 block_size=block_size)
    dense = np.asarray(model(paddle_tpu.Tensor(
        longest[None].astype(np.int64))).numpy())[0]
    check(paged.shape == dense.shape == (len(longest), config.vocab_size)
          and np.isfinite(paged).all() and np.isfinite(dense).all(),
          f"serve: logits {paged.shape} / {dense.shape} malformed")
    max_diff = float(np.max(np.abs(paged - dense)))
    check(max_diff <= logit_tol,
          f"serve: paged prefill logits differ from the dense forward by "
          f"{max_diff:.3e} > {logit_tol:.1e}")
    # the shapes this phase dispatched: decode and prefill chunks over the
    # engine's table, the prefill chunks of the parity check over its own,
    # and the dense forward of the longest prompt
    heads, head_dim = config.num_heads, config.hidden_size // config.num_heads
    f32 = jnp.dtype("float32")
    keys = []
    if tier_policy.policy_mode() == "bench":
        keys = [tier_policy.make_paged_key(t, heads, head_dim, m, block_size,
                                           f32, False)
                for t, m in ((1, per_seq), (prefill_chunk, per_seq),
                             (prefill_chunk, -(-len(longest) // block_size)))]
    record = {
        "phase": "serve", "ok": True,
        "layers": config.num_layers, "hidden": config.hidden_size,
        "prompt_lens": list(prompt_lens), "new_tokens": new_tokens,
        "compile_s": round(compile_s, 2),
        "warmup_ms": {k: round(v, 1) for k, v in engine.warmup_ms.items()},
        "smoke_serve_s": round(serve_s, 3),
        "smoke_ttft_ms": [round(r.ttft_ms(), 1) for r in reqs],
        "smoke_tpot_ms": [round(r.tpot_ms(), 2) for r in reqs],
        "statuses": [r.status for r in reqs],
        "kv": kv,
        "logits_max_abs_diff": max_diff, "logits_tol": logit_tol,
        "logits_max_abs": float(np.max(np.abs(dense))),
        "greedy_agree_frac": float(np.mean(
            paged.argmax(-1) == dense.argmax(-1))),
        "memory": _memory(jax.devices()[:1]),
        "attention": tier_report([(len(longest), head_dim, True)], keys,
                                 fallbacks0),
        "compile_cache": cache_report(cache0),
    }
    emit(record)
    return record


# -- the run -----------------------------------------------------------------

def main() -> None:
    from bench import gpt2_345m_config, require_tpu

    require_tpu("chip_smoke.py")  # before anything is built
    t_start = time.perf_counter()
    emit({"phase": "start", **device_record(),
          "versions": {p: importlib.metadata.version(p)
                       for p in ("jax", "jaxlib", "libtpu")}})
    config = gpt2_345m_config()
    devices = jax.devices()
    one = train_phase("train-1chip", config, devices)
    gc.collect()  # the engine and its state go before the next one comes
    if len(devices) >= 4:
        train_phase("train-4chip", config, devices, mesh_shape=(2, 1, 2),
                    zero_stage=2, reference_losses=one["losses"])
        gc.collect()
    else:
        emit({"phase": "train-4chip", "ran": False,
              "why": f"needs 4 TPU devices, this process sees "
                     f"{len(devices)}"})
    serve_phase(config)
    emit({"phase": "end", "wall_s": round(time.perf_counter() - t_start, 1)})
    emit({"ok": True, "device": device_record()})


if __name__ == "__main__":
    main()
