"""CPU twin of ``chip_smoke.py``: the same phase functions and checks at a
tiny width on the virtual 8-device CPU mesh (no Pallas kernel involved),
plus the rules the smoke relies on — it refuses to run without a TPU, the
compile cache is placed from outside or at one fixed path, a paged tier
the TPU compiler refuses is an error, and a launcher parent opens no device."""
import dataclasses
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

import bench  # noqa: E402
import chip_smoke  # noqa: E402
from paddle_tpu.ops import attention as att  # noqa: E402
from paddle_tpu.ops import tier_policy  # noqa: E402


def _spawn(code_or_args, env=None):
    args = ([sys.executable, "-c", code_or_args]
            if isinstance(code_or_args, str) else code_or_args)
    full = {**os.environ, "PYTHONPATH": REPO, **(env or {})}
    full = {k: v for k, v in full.items() if v is not None}  # None: unset
    return subprocess.Popen(args, stdout=subprocess.PIPE,
                            stderr=subprocess.PIPE, text=True, env=full)


CACHE_CODE = (
    "import jax, paddle_tpu\n"
    "from paddle_tpu.ops import tier_policy\n"
    "from jax._src import xla_bridge\n"
    "assert not xla_bridge.backends_are_initialized()\n"
    "print(jax.config.jax_compilation_cache_dir)\n"
    "print(tier_policy.cache_path())\n"
    "print(jax.config.jax_persistent_cache_min_compile_time_secs,"
    " jax.config.jax_persistent_cache_min_entry_size_bytes)")

PARENT_CODE = (
    "import sys, time\n"
    "from jax._src import xla_bridge\n"
    "from paddle_tpu.distributed.launch import launch\n"
    "from paddle_tpu.profiler.telemetry import get_telemetry\n"
    "rc = launch(sys.argv[1], [], nproc_per_node=2, backend='cpu',"
    " log_dir=sys.argv[2])\n"
    "time.sleep(0.3)\n"
    "get_telemetry().to_jsonl(sys.argv[3])\n"
    "print(rc, xla_bridge.backends_are_initialized())\n")


@pytest.fixture(scope="module")
def fresh(tmp_path_factory):
    """Every fresh interpreter this module needs, run side by side (each
    pays ~2 s of imports): ``{name: (returncode, stdout, stderr)}``, plus
    ``outside_dir``, the directory one of them was told to cache in."""
    tmp = tmp_path_factory.mktemp("fresh")
    (tmp / "w.py").write_text("print('worker')\n")
    outside = str(tmp / "from-outside")
    cpu = {"JAX_PLATFORMS": "cpu"}
    unset = {"JAX_PLATFORMS": "tpu,cpu", "JAX_COMPILATION_CACHE_DIR": None}
    jobs = {script: ([sys.executable, os.path.join(REPO, script)], cpu)
            for script in ("chip_smoke.py", "bench.py", "bench_all.py")}
    jobs.update(
        unset_a=(CACHE_CODE, unset), unset_b=(CACHE_CODE, unset),
        outside=(CACHE_CODE, {**unset, "JAX_COMPILATION_CACHE_DIR": outside}),
        cpu_pinned=(CACHE_CODE, {**unset, **cpu}),
        parent=([sys.executable, "-c", PARENT_CODE, str(tmp / "w.py"),
                 str(tmp / "log"), str(tmp / "parent.jsonl")],
                {**cpu, "PADDLE_TPU_DEVICE_MEM_SAMPLE_EVERY_S": "0.05",
                 "PADDLE_TPU_TELEMETRY_FLUSH_EVERY_S": "0.05",
                 "PADDLE_TPU_TELEMETRY_JSONL": str(tmp / "t.jsonl")}))
    procs = {name: _spawn(*spec) for name, spec in jobs.items()}
    out = {"outside_dir": outside}
    for name, p in procs.items():
        stdout, stderr = p.communicate(timeout=300)
        out[name] = (p.returncode, stdout, stderr)
    return out


def _tiny():
    return dataclasses.replace(bench.gpt2_tiny_config(), num_layers=1)


class TestPhasesOnCpuMesh:
    BATCH, SEQ = 8, 32

    @pytest.fixture(scope="class")
    def one_chip(self):
        return chip_smoke.train_phase(
            "train-1chip", _tiny(), jax.devices(),
            batch=self.BATCH, seq=self.SEQ)

    def test_train_one_device(self, one_chip):
        assert one_chip["ok"] and one_chip["compiles"] == 1
        assert one_chip["mesh"] == {"dp": 1}
        assert len(one_chip["losses"]) == 6
        assert one_chip["losses"][-1] < one_chip["losses"][0]
        # the dense call's tier, read back from its gauge (the CPU's rule)
        assert list(one_chip["attention"]["tiers"].values()) == ["blockwise"]

    def test_train_four_devices_tracks_one_and_is_spread(self, one_chip):
        rec = chip_smoke.train_phase(
            "train-4chip", _tiny(), jax.devices(),
            mesh_shape=(2, 1, 2), zero_stage=2, batch=self.BATCH,
            seq=self.SEQ, reference_losses=one_chip["losses"])
        spread = rec["spread"]
        assert len(spread["state_bytes_per_device"]) == 4
        assert spread["opt_state_bytes_sharded_frac"] >= 0.9
        stems = {k.split("@")[0] for k in spread["collectives"]}
        assert "all-gather" in stems
        assert stems & {"all-reduce", "reduce-scatter"}

    def test_spread_check_catches_state_left_on_one_device(self):
        # the failure the phase exists for: state that never left device 0
        import types

        import numpy as np
        from jax.sharding import Mesh

        mesh = Mesh(np.array(jax.devices()[:4]).reshape(2, 1, 2),
                    ("dp", "mp", "sharding"))
        w = jnp.ones((8, 8))
        stuck = types.SimpleNamespace(_params={"w": w},
                                      _opt_state={"w": {"moment1": w}})
        with pytest.raises(chip_smoke.CheckFailed, match="not spread"):
            chip_smoke._check_spread("t", stuck, mesh)

    def test_serve(self):
        rec = chip_smoke.serve_phase(
            _tiny(), prompt_lens=(8, 16, 33, 70), new_tokens=8,
            decode_buckets=(4,), prefill_chunk=16, block_size=8,
            logit_tol=1e-4)
        assert rec["statuses"] == ["ok"] * 4
        assert rec["kv"]["leaked_blocks"] == 0
        assert rec["attention"]["tier_fallbacks"] == 0
        assert [g.split(".")[1] for g in rec["attention"]["tiers"]] == ["L70"]
        assert rec["logits_max_abs_diff"] <= 1e-4

    def test_a_failed_check_raises(self):
        with pytest.raises(chip_smoke.CheckFailed, match="non-finite"):
            chip_smoke.check(False, "non-finite loss")


def test_measurement_scripts_refuse_to_run_without_a_tpu(fresh):
    for script in ("chip_smoke.py", "bench.py", "bench_all.py"):
        rc, stdout, stderr = fresh[script]
        assert rc != 0, script
        assert stdout == "", script  # no result of any kind
        assert "needs a TPU" in stderr, script


class TestCompileCachePlacement:
    """The rule of ``device.configure_compilation_cache``. Read in fresh
    processes that only import the package: no backend is opened, so an
    accelerator platform list can be named without one being present."""

    @pytest.fixture(scope="class")
    def seen(self, fresh):
        out = {"outside_dir": fresh["outside_dir"]}
        for name in ("unset_a", "unset_b", "outside", "cpu_pinned"):
            rc, stdout, stderr = fresh[name]
            assert rc == 0, (name, stderr)
            out[name] = stdout.split("\n")[:3]
        return out

    def test_unset_is_one_fixed_path_in_the_checkout(self, seen):
        assert seen["unset_a"] == seen["unset_b"]  # two processes, one path
        jax_dir, tiers, thresholds = seen["unset_a"]
        assert jax_dir == os.path.join(REPO, ".compile_cache")
        assert tiers == os.path.join(jax_dir, "attn_tiers.json")
        assert thresholds == "0.0 -1"  # every program is cached

    def test_set_from_outside_stands_and_code_sets_none(self, seen,
                                                        monkeypatch):
        assert seen["outside"][:2] == [
            seen["outside_dir"],
            os.path.join(seen["outside_dir"], "attn_tiers.json")]
        # and in this process: with the variable set, configuring again
        # leaves jax_compilation_cache_dir exactly as it found it
        from paddle_tpu import device

        before = jax.config.jax_compilation_cache_dir
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", seen["outside_dir"])
        device.configure_compilation_cache()
        assert jax.config.jax_compilation_cache_dir == before

    def test_cpu_pinned_process_gets_no_cache_dir(self, seen):
        assert seen["cpu_pinned"][:2] == ["None", "None"]


class TestTierBenchFailureIsLoud:
    """A paged tier that fails in the micro-bench on the TPU: an error
    naming the tier, never a shorter verdict."""

    def test_paged_bench_is_loud_too(self, monkeypatch):
        tier_policy.reset()
        monkeypatch.delenv("PADDLE_TPU_ATTN_TIER_CACHE", raising=False)
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        monkeypatch.setenv("PADDLE_TPU_ATTN_PAGED_POLICY", "bench")

        def refused(*a, **kw):
            raise RuntimeError("scan refused")

        monkeypatch.setattr(att, "_paged_scan_impl", refused)
        with pytest.raises(tier_policy.TierCompileError,
                           match="'paged_scan'.*scan refused"):
            tier_policy.select_paged(1, 2, 8, 4, 4, jnp.float32, False)
        tier_policy.reset()


def test_tpu_backend_with_several_local_ranks_is_an_error(tmp_path):
    from paddle_tpu.distributed.launch import launch

    with pytest.raises(ValueError, match="each open every chip"):
        launch(str(tmp_path / "never_run.py"), [], nproc_per_node=4,
               backend="tpu", log_dir=str(tmp_path / "log"))
    assert not (tmp_path / "log").exists()  # refused before spawning


def test_launcher_parent_opens_no_device(fresh):
    """The supervisor must leave the chips to its children: with the
    device-memory sampler and the periodic flush armed from the
    environment, a launch and a telemetry flush initialise no backend."""
    rc, stdout, stderr = fresh["parent"]
    assert rc == 0, stderr
    assert stdout.split() == ["0", "False"]


def test_unknown_accelerator_has_no_default_peak(monkeypatch):
    from paddle_tpu.profiler import xla_cost

    class Dev:
        device_kind = "TPU v9 mystery"

        def memory_stats(self):
            return None

    xla_cost.reset()
    monkeypatch.setattr(jax, "devices", lambda *a: [Dev()])
    monkeypatch.setattr(jax, "local_devices", lambda *a: [Dev()])
    monkeypatch.delenv("PADDLE_TPU_DEVICE_HBM_BYTES", raising=False)
    try:
        with pytest.raises(ValueError, match="v9 mystery"):
            xla_cost.chip_peaks()
        with pytest.raises(ValueError, match="v9 mystery"):
            xla_cost.hbm_capacity_bytes()
        Dev.device_kind = "TPU v5 lite"  # what the v5e reports
        assert xla_cost.chip_peaks()["flops"] == 197e12
        assert xla_cost.hbm_capacity_bytes() == 16e9
    finally:
        xla_cost.reset()


def test_a_tpu_that_fails_to_initialise_is_not_cpu(monkeypatch):
    from paddle_tpu.core import place

    def failed(kind=None):
        raise RuntimeError("Backend 'tpu' failed to initialize: libtpu "
                           "is in use by another process")

    place._has_tpu.cache_clear()
    monkeypatch.setattr(jax, "devices", failed)
    try:
        with pytest.raises(RuntimeError, match="failed to initialize"):
            place._has_tpu()
    finally:
        place._has_tpu.cache_clear()
    monkeypatch.undo()
    assert place._has_tpu() is False  # no TPU platform at all: parity path
    assert place.TPUPlace(0).jax_device().platform == "cpu"
