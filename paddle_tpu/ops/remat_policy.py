"""Roofline-driven selective rematerialization.

The engines' ``recompute`` flag was all-or-nothing: checkpoint everything
(r5 longctx: −25% throughput paid whether or not the memory was needed)
or nothing (OOM one batch-size later). This module turns the PR 5
attribution layer from a dashboard into a control loop: ``remat='auto'``
on ``jit.TrainStep`` / ``fleet.ParallelTrainStep`` *measures* the
compiled step's peak HBM (``lowered.compile().memory_analysis()`` — the
exact argument+output+temp−alias number behind
``gauge/compile/peak_hbm_bytes``) against the chip's capacity
(``profiler.xla_cost.hbm_capacity_bytes``) and escalates through
``jax.checkpoint`` policies only as far as needed:

- fits → **no remat** (fastest; recompute buys nothing you have room for);
- over budget and the roofline verdict (``gauge/roofline/<entry>``; the
  lowered program's own arithmetic intensity when no prior compile
  exists) says **memory-bound** → jump straight to ``nothing_saveable``
  (the recompute FLOPs are free under the roofline — the step is waiting
  on HBM anyway);
- over budget and **compute-bound** → try ``dots_saveable`` first (keep
  the matmul outputs whose recompute would cost real MXU time, re-derive
  the elementwise/norm/softmax tissue), then ``nothing_saveable``;
- still over → **offload** (``offload_dot_with_no_batch_dims`` to
  pinned_host, where this jax exposes it).

Resolution happens ONCE, at the first step, by lowering+compiling the
candidate programs (the persistent XLA compile cache absorbs the repeat
compiles across restarts; ``PADDLE_TPU_COST_ANALYSIS=0`` disables
measurement and resolves to no-remat with a warning). The chosen policy
is published as ``gauge/remat/<entry>`` (policy id) and
``gauge/remat/peak_hbm/<entry>`` so bench records prove what the control
loop chose and what it cost.

The chunked attention tier settles its own O(L²) residuals
(``ops.attention._REMAT_E``: a hand-written backward makes the exp
weights again); this module decides the transformer-block level question
the engines used to answer with a blanket flag.
"""
from __future__ import annotations

import logging
import os
from typing import Callable, Dict, Optional

import jax

logger = logging.getLogger("paddle_tpu.ops")

__all__ = ["POLICY_IDS", "apply_policy", "program_cost", "resolve",
           "normalize", "layers_checkpointed"]

# stable ids for gauge/remat/<entry> (schema: >= 0). 'layer' is no rung of
# the 'auto' ladder: it is asked for by name
POLICY_IDS = {"off": 0, "dots": 1, "dots_no_batch": 2, "nothing": 3,
              "offload": 4, "full": 5, "layer": 6}

# 'layer': the forward is traced with this flag up, and a model that knows
# its own layer boundaries (``jit.functionalize.checkpointed_call``) puts
# each layer under ``jax.checkpoint``: the step keeps one activation a
# layer and the backward makes a layer's own again when it reaches it.
# ``jax.checkpoint`` around the whole forward ('full') cannot do that: its
# backward holds every layer's activations at once, as no remat does.
_LAYERS_CHECKPOINTED: list = []


def layers_checkpointed() -> bool:
    """True while a forward is traced under ``remat='layer'``."""
    return bool(_LAYERS_CHECKPOINTED)

_warned_off = False


def normalize(remat) -> str:
    """Engine ctor values -> canonical policy name. Accepts the legacy
    ``recompute`` vocabulary (False/True/'dots'/'dots_no_batch'/
    'nothing') plus 'off'/'full'/'offload'/'layer'/'auto'."""
    if remat in (None, False, "off", ""):
        return "off"
    if remat is True or remat == "full":
        return "full"
    name = str(remat)
    if name in POLICY_IDS or name == "auto":
        return name
    raise ValueError(f"unknown remat policy {remat!r}; expected one of "
                     f"{sorted(POLICY_IDS)} or 'auto'")


def _checkpoint_policy(name: str):
    cp = jax.checkpoint_policies
    if name == "dots":
        return cp.checkpoint_dots
    if name == "dots_no_batch":
        return cp.checkpoint_dots_with_no_batch_dims
    if name == "nothing":
        return cp.nothing_saveable
    if name == "offload":
        return cp.offload_dot_with_no_batch_dims("device", "pinned_host")
    raise ValueError(f"no jax.checkpoint policy for {name!r}")


def apply_policy(fn: Callable, policy: str) -> Callable:
    """Wrap a forward-loss callable in the named checkpoint policy
    ('off' returns it untouched, 'full' is plain jax.checkpoint)."""
    policy = normalize(policy)
    if policy == "off":
        return fn
    if policy == "layer":
        def layer_by_layer(*args, **kwargs):
            _LAYERS_CHECKPOINTED.append(True)
            try:
                return fn(*args, **kwargs)
            finally:
                _LAYERS_CHECKPOINTED.pop()

        return layer_by_layer
    if policy == "full":
        return jax.checkpoint(fn, static_argnums=())
    return jax.checkpoint(fn, static_argnums=(),
                          policy=_checkpoint_policy(policy))


def program_cost(jitted, args) -> Optional[Dict[str, float]]:
    """Compile a candidate step and read XLA's own accounting: exact peak
    HBM (argument+output+temp−alias) + flops/bytes for the roofline.
    None when lowering/compilation fails (an infeasible candidate — e.g.
    offload on a backend without pinned_host — is skipped, not fatal)."""
    try:
        lowered = jitted.lower(*args)
        compiled = lowered.compile()
        ca = compiled.cost_analysis()
        if isinstance(ca, (list, tuple)):
            ca = ca[0] if ca else {}
        ca = dict(ca or {})
        mem = compiled.memory_analysis()
        peak = max(
            float(getattr(mem, "argument_size_in_bytes", 0))
            + float(getattr(mem, "output_size_in_bytes", 0))
            + float(getattr(mem, "temp_size_in_bytes", 0))
            - float(getattr(mem, "alias_size_in_bytes", 0)), 0.0)
        return {"peak_hbm_bytes": peak,
                "flops": float(ca.get("flops", 0.0)),
                "bytes_accessed": float(ca.get("bytes accessed", 0.0))}
    except Exception as e:
        logger.info("remat_policy: candidate failed to lower/compile "
                    "(%s: %s)", type(e).__name__, str(e)[:200])
        return None


def budget_bytes() -> float:
    """The peak-HBM budget a step must fit: chip capacity scaled by
    ``PADDLE_TPU_REMAT_BUDGET_FRAC`` (default 0.9 — headroom for the
    allocator, collectives scratch, and prefetched batches)."""
    from ..profiler.xla_cost import hbm_capacity_bytes

    try:
        frac = float(os.environ.get("PADDLE_TPU_REMAT_BUDGET_FRAC", "0.9"))
    except ValueError:
        frac = 0.9
    return hbm_capacity_bytes() * min(max(frac, 0.05), 1.0)


def _verdict_for(entry: str, base_cost: Dict[str, float]) -> str:
    """'compute-bound' | 'memory-bound': a prior compile's registry
    verdict for this entry when one exists (the gauge/roofline/<entry>
    fact), else the candidate program's own intensity vs the machine
    balance point."""
    from ..profiler import xla_cost

    rec = xla_cost.cost_registry().latest().get(entry)
    if rec is not None:
        v = xla_cost.roofline_verdict(rec)
        if v is not None:
            return v
    peaks = xla_cost.chip_peaks()
    if base_cost["bytes_accessed"] <= 0 or peaks["bytes_per_s"] <= 0:
        return "compute-bound"
    intensity = base_cost["flops"] / base_cost["bytes_accessed"]
    return ("compute-bound"
            if intensity >= peaks["flops"] / peaks["bytes_per_s"]
            else "memory-bound")


def resolve(entry: str, lower_cost: Callable[[str], Optional[Dict]],
            telemetry=None) -> str:
    """Pick the cheapest policy whose measured peak HBM fits the budget.

    ``lower_cost(policy)`` must return ``program_cost`` of the step built
    with that policy (or None if infeasible). Returns the chosen policy
    name and publishes ``gauge/remat/<entry>`` +
    ``gauge/remat/peak_hbm/<entry>``."""
    from ..profiler.telemetry import get_telemetry
    from ..profiler.xla_cost import cost_analysis_mode

    global _warned_off
    tel = telemetry or get_telemetry()

    def publish(policy: str, peak: Optional[float]) -> str:
        tel.gauge(f"remat/{entry}", POLICY_IDS[policy])
        if peak is not None:
            tel.gauge(f"remat/peak_hbm/{entry}", peak)
        return policy

    if cost_analysis_mode() == "off":
        if not _warned_off:
            _warned_off = True
            logger.warning(
                "remat_policy: PADDLE_TPU_COST_ANALYSIS=0 — remat='auto' "
                "cannot measure peak HBM and resolves to no remat; set a "
                "policy explicitly if this OOMs")
        return publish("off", None)
    budget = budget_bytes()
    base = lower_cost("off")
    if base is None:
        logger.warning("remat_policy: could not cost the no-remat step for "
                       "%s — resolving to no remat", entry)
        return publish("off", None)
    if base["peak_hbm_bytes"] <= budget:
        logger.info("remat_policy: %s peak %.2f GB fits budget %.2f GB — "
                    "no remat", entry, base["peak_hbm_bytes"] / 1e9,
                    budget / 1e9)
        return publish("off", base["peak_hbm_bytes"])
    verdict = _verdict_for(entry, base)
    ladder = (["nothing", "offload"] if verdict == "memory-bound"
              else ["dots", "nothing", "offload"])
    best_policy, best_peak = "off", base["peak_hbm_bytes"]
    for policy in ladder:
        try:
            cost = lower_cost(policy)
        except Exception as e:
            # apply_policy/_checkpoint_policy can raise BEFORE program_cost's
            # own try (e.g. a jax without offload_dot_with_no_batch_dims) —
            # an unavailable candidate is skipped, never fatal
            logger.info("remat_policy: candidate %r unavailable on this "
                        "jax (%s: %s)", policy, type(e).__name__,
                        str(e)[:200])
            cost = None
        if cost is None:
            continue
        peak = cost["peak_hbm_bytes"]
        if peak < best_peak:
            best_policy, best_peak = policy, peak
        if peak <= budget:
            logger.info(
                "remat_policy: %s (%s) over budget at %.2f GB — policy "
                "%r fits at %.2f GB (budget %.2f GB)", entry, verdict,
                base["peak_hbm_bytes"] / 1e9, policy, peak / 1e9,
                budget / 1e9)
            return publish(policy, peak)
    logger.warning(
        "remat_policy: %s (%s): no policy fits the %.2f GB budget — "
        "taking the smallest measured peak (%r at %.2f GB); expect "
        "allocator pressure", entry, verdict, budget / 1e9, best_policy,
        best_peak / 1e9)
    return publish(best_policy, best_peak)
