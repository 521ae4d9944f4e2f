"""paddle_tpu.inference.serving — overload-safe TPU request serving.

The runtime around the AOT ``inference.Predictor``: a bounded admission
queue with explicit load shedding, per-request deadlines enforced at
enqueue / batch formation / completion, a continuous-batching scheduler
dispatching batch-size-bucketed AOT executables (compile count bounded
by ``len(buckets)``, persisted across restarts by the PR 2 compile
cache), and the resilience stack wired through the serve loop: watchdog
heartbeats per batch, SIGTERM → drain → exit 77 for elastic relaunch,
and request-level fault injection (``slow_req@`` / ``drop_req@`` /
``deadline_storm@``). See README "Serving runtime".

    from paddle_tpu.inference import Config, create_predictor
    from paddle_tpu.inference.serving import ServeConfig, ServingEngine

    predictor = create_predictor(Config("model"))      # .pdexport
    engine = ServingEngine(predictor, ServeConfig(
        capacity=64, buckets=(1, 2, 4, 8), default_deadline_s=0.5))
    engine.install_preemption().start()
    req = engine.submit([x], deadline_s=0.2)           # per-sample input
    req.wait()
    if req.status == "ok":
        y = req.outputs[0]
"""
from .admission import AdmissionQueue
from .decode import (DecodeScheduler, GenRequest, TokenServeConfig,
                     TokenServingEngine, dense_greedy_reference,
                     paged_prefill_logits)
from .engine import ServeConfig, ServingEngine
from .kv_cache import KVCacheConfig, KVCachePool
from .loadgen import (run_generation_streams, run_load, run_streams,
                      summarize, summarize_generation)
from .request import Request, RequestStatus
from .scheduler import BatchScheduler

__all__ = [
    "AdmissionQueue", "BatchScheduler", "DecodeScheduler", "GenRequest",
    "KVCacheConfig", "KVCachePool", "Request", "RequestStatus",
    "ServeConfig", "ServingEngine", "TokenServeConfig",
    "TokenServingEngine", "dense_greedy_reference", "paged_prefill_logits",
    "run_generation_streams", "run_load", "run_streams", "summarize",
    "summarize_generation",
]
