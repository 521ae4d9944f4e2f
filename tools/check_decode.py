#!/usr/bin/env python
"""Token-level decode-serving gate — continuous batching, paged KV, and
speculative decoding are exercised end-to-end, not claimed.

Two phases, both on the CPU backend against the REAL runtime
(``inference.serving.TokenServingEngine``, no mocks):

1. **Parity** (in-process): greedy generation through the paged decode
   path (chunked prefill + decode-step continuous batching + speculative
   drafting) must produce EXACTLY the tokens of the dense
   recompute-the-prefix reference, and the paged prefill's logits must
   match the Layer model's full forward within tolerance — the paged KV
   cache is an optimization, never a numerics fork.

2. **Mixed load + drain** (subprocess, so the preemption exit code is
   observable): short and long prompts (prefill chunking active) at
   N concurrent streams, injected ``slow_req`` stragglers, and a real
   mid-load SIGTERM. Asserts: exit 77 via the drain path; EVERY request
   terminal exactly once (zero unaccounted, zero double-terminal, OK
   with full text or DRAINED with partial text); ZERO leaked KV blocks
   (target AND draft pool); bounded TTFT p99; telemetry schema-valid
   including the new ``serve/kv_*``, ``serve/spec_accept_rate``, and
   TTFT/TPOT contracts; zero ``counter/attn/tier_fallbacks``.

Gate conventions per tools/_gate.py (``decode: OK|FAIL — ...``, exit
0/1, ``--json``). Wired into tools/bench_ritual.sh after check_serving.
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import tempfile
import textwrap

_TOOLS = os.path.dirname(os.path.abspath(__file__))
_REPO = os.path.dirname(_TOOLS)
sys.path.insert(0, _TOOLS)
if _REPO not in sys.path:
    sys.path.insert(1, _REPO)
from _gate import add_gate_args, finish, read_counters  # noqa: E402

EXIT_PREEMPTED = 77


def _tiny_models():
    import paddle_tpu as paddle
    from paddle_tpu.text.models.gpt import GPTConfig, GPTForCausalLM

    paddle.seed(0)
    cfg = GPTConfig(vocab_size=128, hidden_size=32, num_layers=2,
                    num_heads=2, max_position_embeddings=128,
                    hidden_dropout=0.0, attention_dropout=0.0)
    model = GPTForCausalLM(cfg)
    model.eval()
    paddle.seed(3)
    dcfg = GPTConfig(vocab_size=128, hidden_size=16, num_layers=1,
                     num_heads=2, max_position_embeddings=128,
                     hidden_dropout=0.0, attention_dropout=0.0)
    draft = GPTForCausalLM(dcfg)
    draft.eval()
    return model, draft


def check_parity():
    """Phase 1: paged == dense, tokens exactly, logits within tolerance.
    Returns (ok, detail)."""
    import numpy as np
    import paddle_tpu
    from paddle_tpu.inference.serving import (TokenServeConfig,
                                              TokenServingEngine,
                                              dense_greedy_reference,
                                              paged_prefill_logits)

    model, draft = _tiny_models()
    rng = np.random.RandomState(11)
    prompts = [rng.randint(0, 128, n).astype(np.int32)
               for n in (4, 9, 21, 33)]

    # logit parity: chunked paged prefill vs the Layer model's forward
    prompt = prompts[3]
    paged_logits = paged_prefill_logits(model, prompt, chunk=8,
                                        block_size=8)
    ref_logits = np.asarray(model(
        paddle_tpu.Tensor(prompt[None].astype(np.int64))).numpy())[0]
    max_diff = float(np.max(np.abs(paged_logits - ref_logits)))
    if max_diff > 1e-4:
        return False, (f"paged prefill logits diverge from the dense "
                       f"forward: max |diff| = {max_diff:.2e} > 1e-4")

    # token parity: plain AND speculative engines vs dense reference
    for label, kw in (("plain", {}),
                      ("spec", {"draft_model": draft})):
        eng = TokenServingEngine(model, TokenServeConfig(
            capacity=16, decode_buckets=(1, 2, 4), prefill_chunk=8,
            kv_blocks=48, kv_block_size=8, max_seq_len=96,
            spec_k=3 if label == "spec" else 0), **kw)
        eng.start()
        try:
            reqs = [eng.submit(p, max_new_tokens=12) for p in prompts]
            for r in reqs:
                r.wait(120)
            for p, r in zip(prompts, reqs):
                if r.status != "ok":
                    return False, f"{label}: request ended {r.status!r}"
                ref = dense_greedy_reference(model, p, 12)
                got = [int(t) for t in r.outputs[0]]
                if got != ref:
                    return False, (f"{label}: greedy tokens diverge from "
                                   f"the dense reference for a "
                                   f"{len(p)}-token prompt: {got} != {ref}")
        finally:
            eng.shutdown()
        kv = eng.kv_accounting()
        if kv["leaked_blocks"] or kv.get("draft", {}).get("leaked_blocks"):
            return False, f"{label}: leaked KV blocks after shutdown: {kv}"
    return True, (f"paged==dense: logits within {max_diff:.1e}, greedy "
                  f"tokens identical (plain + speculative), zero leaks")


# Phase 2 worker: mixed prefill+decode load with stragglers, drained by a
# real mid-load SIGTERM, accounting + KV ledger written for the gate.
WORKER = textwrap.dedent("""
    import json, os, sys
    import numpy as np
    import paddle_tpu as paddle
    from paddle_tpu.text.models.gpt import GPTConfig, GPTForCausalLM
    from paddle_tpu.inference.serving import (TokenServeConfig,
                                              TokenServingEngine,
                                              run_generation_streams)
    from paddle_tpu.inference.serving.loadgen import summarize_generation
    from paddle_tpu.profiler.telemetry import get_telemetry

    TEL = os.environ["DEMO_TELEMETRY"]
    RESULT = os.environ["DEMO_RESULT"]

    paddle.seed(0)
    cfg = GPTConfig(vocab_size=128, hidden_size=32, num_layers=2,
                    num_heads=2, max_position_embeddings=128,
                    hidden_dropout=0.0, attention_dropout=0.0)
    model = GPTForCausalLM(cfg); model.eval()
    paddle.seed(3)
    dcfg = GPTConfig(vocab_size=128, hidden_size=16, num_layers=1,
                     num_heads=2, max_position_embeddings=128,
                     hidden_dropout=0.0, attention_dropout=0.0)
    draft = GPTForCausalLM(dcfg); draft.eval()

    eng = TokenServingEngine(model, TokenServeConfig(
        capacity=16, decode_buckets=(1, 2, 4), max_running=4,
        prefill_chunk=8, kv_blocks=64, kv_block_size=8, max_seq_len=96,
        drain_grace_s=2.0, spec_k=2), draft_model=draft)
    eng.install_preemption().start()

    rng = np.random.RandomState(0)
    # mixed shape: short prompts decode while long prompts chunk-prefill
    lengths = [3, 30, 7, 45, 12, 26, 5, 38]
    prompts = [rng.randint(0, 128, n).astype(np.int32) for n in lengths]

    all_reqs, rounds = [], 0
    while not eng.draining and rounds < 40:
        out = run_generation_streams(
            eng, n_streams=4, requests_per_stream=2,
            prompt_fn=lambda k: prompts[k % len(prompts)],
            max_new_tokens=24)
        rounds += 1
    # collect EVERY request the engine saw via its ledger; per-request
    # stamps come from the loadgen summaries already folded per round
    drained = eng.wait_drained(30.0) if eng.draining else False
    acct = eng.accounting()
    with open(RESULT, "w") as f:
        json.dump({"accounting": acct,
                   "kv": eng.kv_accounting(),
                   "rounds": rounds,
                   "drained": drained,
                   "drain_reason": eng.drain_reason}, f)
    tel = get_telemetry()
    eng.exit_if_preempted(save_fn=lambda: tel.to_jsonl(
        TEL, tag="decode_demo"))
    sys.exit(4)  # injected SIGTERM never arrived: the plan did not run
""")


def run_demo(workdir, sigterm_batch=60):
    result_path = os.path.join(workdir, "result.json")
    tel_path = os.path.join(workdir, "TELEMETRY.jsonl")
    worker = os.path.join(workdir, "worker.py")
    with open(worker, "w") as f:
        f.write(WORKER)
    # stragglers stall decode rounds mid-load; the SIGTERM lands at a
    # scheduler-iteration boundary the load certainly reaches
    inject = ("slow_req@5:0.3,slow_req@11:0.3,"
              f"sigterm@{sigterm_batch}")
    env = {
        **os.environ,
        "JAX_PLATFORMS": "cpu",
        "PYTHONPATH": _REPO + os.pathsep + os.environ.get("PYTHONPATH", ""),
        "PADDLE_TPU_TELEMETRY": "1",
        "PADDLE_TPU_INJECT": inject,
        "PADDLE_TPU_INJECT_STATE": os.path.join(workdir, "inject-state"),
        "DEMO_TELEMETRY": tel_path,
        "DEMO_RESULT": result_path,
    }
    r = subprocess.run([sys.executable, worker], env=env,
                       capture_output=True, text=True, timeout=600)
    payload = {"returncode": r.returncode, "inject": inject}
    if r.returncode != EXIT_PREEMPTED:
        return False, (f"worker exited rc={r.returncode}, expected "
                       f"EXIT_PREEMPTED={EXIT_PREEMPTED} (drain path): "
                       f"{r.stderr[-400:]}"), payload
    if not os.path.exists(result_path):
        return False, "worker exited 77 but wrote no ledger", payload
    with open(result_path) as f:
        result = json.load(f)
    acct = result["accounting"]
    kv = result["kv"]
    payload.update({"by_status": acct["by_status"],
                    "submitted": acct["submitted"],
                    "kv": kv, "rounds": result["rounds"]})
    if acct["unaccounted"]:
        return False, (f"{len(acct['unaccounted'])} request(s) lack a "
                       f"terminal status: {acct['unaccounted'][:5]}"), payload
    if acct["double_terminal"]:
        return False, (f"double_terminal = {acct['double_terminal']} — a "
                       "request was claimed twice"), payload
    if acct["by_status"].get("ok", 0) < 1:
        return False, f"no request completed OK: {acct['by_status']}", payload
    if kv["leaked_blocks"] != 0 or kv.get("draft", {}).get("leaked_blocks"):
        return False, (f"KV pool leaked blocks through the drain: {kv}"), \
            payload

    from check_telemetry_schema import validate_file

    n, err = validate_file(
        tel_path,
        require=["counter/serve/requests",
                 "counter/serve/kv_blocks_alloc",
                 "counter/serve/kv_blocks_free",
                 "counter/serve/tokens_generated",
                 "gauge/serve/kv_occupancy",
                 "gauge/serve/spec_accept_rate",
                 "counter/resilience/preempt_exits"],
        require_prefix=["hist/serve/ttft_ms", "hist/serve/tpot_ms",
                        # the worker serves speculatively, so its decode
                        # steps are verify steps (plain decode_ms is
                        # covered by the non-spec bench config)
                        "hist/serve/verify_ms", "hist/serve/prefill_ms"])
    if err:
        return False, f"telemetry: {err}", payload
    counters = read_counters(tel_path)
    if counters.get("counter/serve/double_terminal", 0) != 0:
        return False, "counter/serve/double_terminal != 0", payload
    if counters.get("counter/attn/tier_fallbacks", 0) != 0:
        return False, "counter/attn/tier_fallbacks != 0 over the decode " \
            "run — a decode shape silently rerouted off its tier", payload
    # alloc/free must balance: every block allocated over the whole run
    # was freed by a terminal transition (cross-checks the ledger above)
    alloc = counters.get("counter/serve/kv_blocks_alloc", 0)
    freed = counters.get("counter/serve/kv_blocks_free", 0)
    if alloc != freed:
        return False, (f"kv_blocks_alloc ({alloc}) != kv_blocks_free "
                       f"({freed}) after drain"), payload
    # bounded TTFT: p99 of time-to-first-token over the run (from the
    # telemetry hist the scheduler records per retired request)
    ttft_bound_ms = float(os.environ.get("DECODE_GATE_TTFT_BOUND_MS",
                                         "5000"))
    ttft_p99 = None
    with open(tel_path) as f:
        for line in f:
            line = line.strip()
            if not line:
                continue
            rec = json.loads(line)
            v = rec.get("scalars", {}).get("hist/serve/ttft_ms/p99")
            if v is not None:
                ttft_p99 = v
    payload["ttft_p99_ms"] = ttft_p99
    if ttft_p99 is None:
        return False, "no hist/serve/ttft_ms/p99 in telemetry", payload
    if ttft_p99 > ttft_bound_ms:
        return False, (f"TTFT p99 {ttft_p99:.0f} ms exceeds the "
                       f"{ttft_bound_ms:.0f} ms bound — admission is "
                       "stalling first tokens"), payload
    return True, (f"mixed load drained cleanly: {acct['by_status']} of "
                  f"{acct['submitted']}, TTFT p99 {ttft_p99:.0f} ms, "
                  f"kv alloc==free=={alloc}, exit 77"), payload


def main(argv=None):
    ap = argparse.ArgumentParser(
        description="Token-level decode serving gate: paged-vs-dense "
                    "parity + mixed prefill/decode load with stragglers "
                    "and a mid-generation SIGTERM drain")
    ap.add_argument("--sigterm-batch", type=int, default=60)
    ap.add_argument("--skip-parity", action="store_true",
                    help="only run the subprocess drain phase")
    ap.add_argument("--workdir", default=None)
    add_gate_args(ap)
    args = ap.parse_args(argv)
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    if not args.skip_parity:
        ok, detail = check_parity()
        if not ok:
            return finish("decode", False, detail, json_mode=args.json)
        parity_detail = detail
    else:
        parity_detail = "parity skipped"
    if args.workdir:
        os.makedirs(args.workdir, exist_ok=True)
        ok, detail, payload = run_demo(args.workdir,
                                       sigterm_batch=args.sigterm_batch)
    else:
        with tempfile.TemporaryDirectory(prefix="decode-gate-") as d:
            ok, detail, payload = run_demo(d,
                                           sigterm_batch=args.sigterm_batch)
    return finish("decode", ok, f"{parity_detail}; {detail}",
                  payload=payload, json_mode=args.json)


if __name__ == "__main__":
    sys.exit(main())
