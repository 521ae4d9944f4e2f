"""Kimi-Linear against its plain reference (``benchmark/reference/
kimi_linear.py``) on seeded random weights, at a small size on the CPU:
the expert layer's share, the shares adding up to the uncut layer, MLA,
the norms and the gated MLP, the whole model's loss and gradients, the
train step through ``fleet.ParallelTrainStep``, and the names of its parts
in the compiled step.

Tolerances. Both sides are float32 on the CPU and part by the order of
their sums: a layer's output by a few 1e-7 of its largest value, the whole
model's gradients by up to 2e-5 (five layers of accumulated rounding,
measured 1e-6 to 8e-6). ``TOL`` = 1e-4 holds those and fails what the
tests plant: a token dropped from one expert moves the layer's output by
1e-2 or more, bfloat16 weights move the loss's gradients by 1e-3 or more.
"""
import collections
import contextlib
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh

import paddle_tpu as paddle
from benchmark.families import kimi_linear as family
from benchmark.lib import inner_scopes, layout
from benchmark.reference import common
from benchmark.reference import kimi_linear as reference
from paddle_tpu import nn
from paddle_tpu.distributed.fleet.engine import ParallelTrainStep
from paddle_tpu.incubate.moe import (DroplessMoE, held_experts_part,
                                     publish_moe_stats, route_top_k)
from paddle_tpu.jit.functionalize import functionalize, get_params, set_params
from paddle_tpu.profiler import get_telemetry, hlo_attrib
from paddle_tpu.text.models.kimi_linear import (KimiLinearConfig,
                                                KimiLinearForCausalLM,
                                                KimiMLAttention,
                                                kimi_linear_tiny)

TOL = 1e-4
F32 = jnp.float32
EINSUM = common.make_einsum("float32")

# the reference's configuration of a tiny model: three layers, every kind
# (KDA + dense, KDA + experts, MLA + experts), 4 of 16 experts held
CONFIG = {
    "first_k_dense_replace": 1, "hidden_size": 64, "intermediate_size": 128,
    "kv_lora_rank": 32,
    "linear_attn_config": {"full_attn_layers": [3], "kda_layers": [1, 2],
                           "head_dim": 16, "num_heads": 2,
                           "short_conv_kernel_size": 4},
    "moe_intermediate_size": 32, "moe_renormalize": True,
    "num_attention_heads": 2, "num_experts": 4, "num_experts_per_token": 4,
    "num_hidden_layers": 3, "num_shared_experts": 1, "qk_nope_head_dim": 16,
    "qk_rope_head_dim": 8, "rms_norm_eps": 1e-5,
    "routed_scaling_factor": 2.446, "v_head_dim": 16, "vocab_size": 128,
    "deployment": {"num_experts_routed": 16, "experts_held": [0, 4],
                   "vocab_size_published": 1024},
    "assumed": {"gate_rank": 8, "initializer_range": 0.02, "conv_std": 0.2887,
                "dt_bias_std": 3.0, "l2_norm_eps": 1e-6},
}


def program_config(**kw):
    lin, dep = CONFIG["linear_attn_config"], CONFIG["deployment"]
    base = dict(
        vocab_size=dep["vocab_size_published"],
        vocab_rows_held=CONFIG["vocab_size"], hidden_size=64,
        num_hidden_layers=3, intermediate_size=128, moe_intermediate_size=32,
        num_experts=16, experts_held=range(0, 4), num_experts_per_token=4,
        num_attention_heads=2, qk_nope_head_dim=16, qk_rope_head_dim=8,
        v_head_dim=16, kv_lora_rank=32, kda_layers=tuple(lin["kda_layers"]),
        full_attn_layers=tuple(lin["full_attn_layers"]), kda_num_heads=2,
        kda_head_dim=16, gate_rank=8)
    base.update(kw)
    return KimiLinearConfig(**base)


def worst(got, want):
    got, want = jnp.asarray(got, F32), jnp.asarray(want, F32)
    return float(jnp.max(jnp.abs(got - want)) / jnp.max(jnp.abs(want)))


def normal(seed, *shape, std=1.0):
    return std * jax.random.normal(jax.random.PRNGKey(seed), shape, F32)


# -- the expert layer ----------------------------------------------------------

def expert_weights(seed, routed, h=32, f=16):
    return {"gate_w": normal(seed, routed, h, f, std=0.3),
            "up_w": normal(seed + 1, routed, h, f, std=0.3),
            "down_w": normal(seed + 2, routed, f, h, std=0.3)}


def test_the_shares_add_up_to_the_uncut_layer():
    """What all the shares give, the shared expert counted once, is what
    the reference gives for the whole layer; with a selection bias that is
    not zero, so that it is seen to steer the choice and not the weights."""
    routed, shares, top_k, h, f = 16, 4, 4, 32, 16
    held = routed // shares
    x = normal(0, 1, 24, h)
    router = normal(1, h, routed, std=0.5)
    bias = normal(2, routed, std=0.5)
    everyone = expert_weights(3, routed)
    shared = {n: w[0] for n, w in expert_weights(7, 1).items()}
    z = {"top_k": top_k, "scale": 2.446}
    want = reference.uncut_moe(x, router, bias, everyone, shared, z, EINSUM)
    unbiased = reference.uncut_moe(x, router, None, everyone, shared, z,
                                   EINSUM)
    assert worst(unbiased, want) > 1e-2     # the bias changed the choice

    total = jnp.zeros_like(x[0])
    for rank in range(shares):
        layer = DroplessMoE(h, f, routed,
                            experts_held=range(rank * held, (rank + 1) * held),
                            top_k=top_k, scale=2.446, shared_experts=1)
        own = slice(rank * held, (rank + 1) * held)
        set_params(layer, {
            "gate.weight": router, "w_gate": everyone["gate_w"][own],
            "w_up": everyone["up_w"][own], "w_down": everyone["down_w"][own],
            "shared.gate_proj.weight": shared["gate_w"],
            "shared.up_proj.weight": shared["up_w"],
            "shared.down_proj.weight": shared["down_w"]})
        layer.select_bias._value = bias
        part = layer(paddle.to_tensor(x[0]))._value
        shared_part = layer.shared(paddle.to_tensor(x[0]))._value
        # every chip computes the shared expert alike: count it once
        total = total + part - (shared_part if rank else 0.0)
    assert worst(total, want[0]) < TOL


def test_the_held_part_matches_the_reference_and_drops_nothing():
    t, h, f, routed, held, top_k = 40, 32, 16, 16, 4, 4
    x = normal(0, t, h)
    scores = jax.nn.sigmoid(normal(1, t, routed))
    chosen, weights = route_top_k(scores, jnp.zeros(routed), top_k, 2.446)
    stack = expert_weights(5, held)
    got, stats = held_experts_part(x, chosen, weights, stack["gate_w"],
                                   stack["up_w"], stack["down_w"], first=4)
    want = reference.routed_part(x[None], chosen[None], weights[None], stack,
                                 4, EINSUM)[0]
    assert worst(got, want) < TOL
    here = int(jnp.sum((chosen >= 4) & (chosen < 8)))
    assert float(stats[0]) == pytest.approx(here / (t * top_k))
    assert float(stats[2]) == 0.0
    # a dropped pair would show: leave one token out of one held expert
    t0, j0 = (int(i) for i in jnp.argwhere((chosen >= 4) & (chosen < 8))[0])
    e = int(chosen[t0, j0]) - 4
    lost = weights[t0, j0] * reference.gated_mlp(
        x[None, t0:t0 + 1], stack["gate_w"][e], stack["up_w"][e],
        stack["down_w"][e], EINSUM)[0, 0]
    assert float(jnp.max(jnp.abs(lost)) / jnp.max(jnp.abs(want))) > TOL


def test_every_token_on_the_held_experts_is_still_exact():
    """The worst case the pair buffer is sized for: every token chooses
    all the held experts."""
    t, h, f, routed, held = 16, 32, 16, 8, 4
    x = normal(0, t, h)
    scores = jax.nn.sigmoid(normal(1, t, routed))
    bias = jnp.where(jnp.arange(routed) < held, 10.0, 0.0)
    chosen, weights = route_top_k(scores, bias, held, 1.0)
    assert bool(jnp.all(chosen < held))
    stack = expert_weights(5, held)
    got, stats = held_experts_part(x, chosen, weights, stack["gate_w"],
                                   stack["up_w"], stack["down_w"], first=0)
    want = reference.routed_part(x[None], chosen[None], weights[None], stack,
                                 0, EINSUM)[0]
    assert worst(got, want) < TOL
    assert float(stats[0]) == 1.0 and float(stats[2]) == 0.0


def test_held_part_gradients_match_the_reference():
    t, h, f, routed, held, top_k = 24, 32, 16, 8, 4, 2
    x = normal(0, t, h)
    router = normal(1, h, routed, std=0.3)
    stack = expert_weights(5, held)
    ct = normal(9, t, h)

    def ours(x, router, stack):
        chosen, weights = route_top_k(jax.nn.sigmoid(x @ router),
                                      jnp.zeros(routed), top_k, 2.446)
        return jnp.sum(ct * held_experts_part(
            x, chosen, weights, stack["gate_w"], stack["up_w"],
            stack["down_w"], first=2)[0])

    def theirs(x, router, stack):
        z = {"top_k": top_k, "scale": 2.446}
        chosen, weights = reference.route(x[None], router, None, z, EINSUM)
        return jnp.sum(ct * reference.routed_part(
            x[None], chosen, weights, stack, 2, EINSUM)[0])

    got = jax.grad(ours, argnums=(0, 1, 2))(x, router, stack)
    want = jax.grad(theirs, argnums=(0, 1, 2))(x, router, stack)
    for a, b in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        assert worst(a, b) < TOL


def test_experts_held_has_to_be_a_run_of_the_routed_ones():
    with pytest.raises(ValueError, match="experts_held"):
        DroplessMoE(8, 4, 8, experts_held=range(6, 10))
    with pytest.raises(ValueError, match="experts_held"):
        DroplessMoE(8, 4, 8, experts_held=range(0, 8, 2))


def test_stats_reach_telemetry_when_asked():
    tel = get_telemetry()
    tel.reset()
    layer = DroplessMoE(32, 16, 16, experts_held=range(4, 8), top_k=4,
                        scale=2.446)
    layer(paddle.to_tensor(normal(0, 2, 20, 32)))
    assert tel.scalars()["gauge/moe/experts_held"] == 4
    out = publish_moe_stats(layer)
    (stats,) = out.values()
    scalars = tel.scalars()
    assert scalars["gauge/moe/pairs_here_share"] == pytest.approx(
        stats["pairs_here_share"])
    assert 0.05 < stats["pairs_here_share"] < 0.6   # near 4 / 16
    assert scalars["gauge/moe/load_max_over_mean"] >= 1.0
    assert tel.counter_value("moe/dropped_pairs") == 0
    tel.reset()


# -- the other new layers --------------------------------------------------------

def test_rms_norms_and_the_gated_mlp():
    x = normal(0, 2, 5, 4, 16)
    norm = nn.RMSNorm(16, epsilon=1e-5)
    norm.weight._value = normal(1, 16)
    want = reference.rms_norm(x, norm.weight._value, 1e-5)
    assert worst(norm(paddle.to_tensor(x))._value, want) < 1e-6
    gated = nn.GatedRMSNorm(16, epsilon=1e-5)
    gated.weight._value = norm.weight._value
    gate = normal(2, 2, 5, 64)               # the heads flattened
    got = gated(paddle.to_tensor(x), paddle.to_tensor(gate))._value
    assert worst(got, want * jax.nn.sigmoid(gate.reshape(x.shape))) < 1e-6
    mlp = nn.SwiGLU(16, 24)
    p = get_params(mlp)
    want = reference.gated_mlp(x[:, :, 0], p["gate_proj.weight"],
                               p["up_proj.weight"], p["down_proj.weight"],
                               EINSUM)
    assert worst(mlp(paddle.to_tensor(x[:, :, 0]))._value, want) < 1e-5
    # bf16 in, bf16 out, the statistic in f32
    assert norm(paddle.to_tensor(x.astype(jnp.bfloat16)))._value.dtype \
        == jnp.bfloat16


def test_mla_matches_the_reference_with_unequal_head_widths():
    get_telemetry().reset()
    cfg = program_config()
    layer = KimiMLAttention(cfg)
    p = get_params(layer)
    x = normal(0, 2, 48, 64)
    z = reference.sizes(CONFIG)
    want = reference.mla(x, {
        "q_w": p["q_proj.weight"], "kva_w": p["kv_a_proj.weight"],
        "kv_norm": p["kv_a_norm.weight"], "kvb_w": p["kv_b_proj.weight"],
        "o_w": p["o_proj.weight"]}, z, EINSUM)
    assert worst(layer(paddle.to_tensor(x))._value, want) < TOL
    # q and k 24 wide, v 16: the XLA chunk body by rule, and no race
    from paddle_tpu.ops import tier_policy

    assert get_telemetry().scalars()["gauge/attn/tier.L48.d24.c"] == \
        tier_policy.TIER_IDS["xla"]
    assert get_telemetry().counter_value("attn/tier_bench") == 0
    get_telemetry().reset()


@pytest.mark.parametrize("heads,d", [(4, 16), (2, 128)])
@pytest.mark.parametrize("dtype", [F32, jnp.bfloat16])
def test_the_gated_norm_on_the_flat_form_is_the_norm_by_heads(heads, d, dtype):
    """``F.rms_norm(..., gate=...)`` computes on [b, l, heads d] (a head's
    sums are products with a 0/1 matrix): value and the gradients of x,
    weight and gate against the mean of squares over the last axis of the
    [b, l, heads, d] form, in f32 to rounding, in bf16 to bf16's."""
    x = normal(3, 2, 7, heads, d).astype(dtype)
    gate = normal(4, 2, 7, heads * d).astype(dtype)
    weight = 1.0 + 0.1 * normal(5, d)
    ct = normal(6, 2, 7, heads, d)

    def by_heads(x, weight, gate):
        out = reference.rms_norm(x.astype(F32), weight, 1e-5) \
            * jax.nn.sigmoid(gate.astype(F32).reshape(x.shape))
        return out.astype(x.dtype)

    flat = lambda x, weight, gate: paddle.nn.functional.rms_norm(  # noqa: E731
        paddle.to_tensor(x), paddle.to_tensor(weight), 1e-5,
        gate=paddle.to_tensor(gate))._value
    both_of = lambda f: jax.vjp(f, x, weight, gate)  # noqa: E731
    (want, want_vjp), (got, got_vjp) = both_of(by_heads), both_of(flat)
    assert got.shape == x.shape and got.dtype == dtype
    tol = 1e-6 if dtype == F32 else 1e-2
    assert worst(got.astype(F32), want.astype(F32)) < tol
    for name, a, b in zip(("x", "weight", "gate"), got_vjp(ct.astype(dtype)),
                          want_vjp(ct.astype(dtype))):
        assert a.dtype == b.dtype and a.shape == b.shape, name
        assert worst(a.astype(F32), b.astype(F32)) < 10 * tol, name


# -- the whole model ---------------------------------------------------------------

def model_and_weights(seed=3):
    specs = reference.param_specs(CONFIG)
    params = common.init_params(specs, seed)
    names = family.names_of(CONFIG)
    model = KimiLinearForCausalLM(program_config())
    assert set(names.values()) == set(get_params(model))
    set_params(model, layout.to_program(names, specs, params))
    return model, params, names


def batch(seed=0, rows=2, length=24):
    ids = np.random.default_rng(seed).integers(0, CONFIG["vocab_size"],
                                               (rows, length), dtype=np.int32)
    return {"ids": jnp.asarray(ids), "labels": jnp.asarray(np.roll(ids, -1, 1))}


def test_loss_and_gradients_match_the_reference():
    model, params, names = model_and_weights()
    data = batch()
    denoms = reference.denominators(data)
    want_loss, want = jax.jit(jax.value_and_grad(
        lambda p: reference.block_loss(p, data, denoms, CONFIG, EINSUM)))(
            params)
    apply = functionalize(model, training=True)
    buffers = {n: b._value for n, b in model.named_buffers()}
    got_loss, got = jax.jit(jax.value_and_grad(
        lambda p: apply(p, buffers, data["ids"], data["labels"])[0]))(
            get_params(model))
    assert abs(float(got_loss) - float(want_loss)) < 1e-5 * float(want_loss)
    for leaf, name in names.items():
        assert worst(got[name], want[leaf]) < TOL, leaf
    # the comparison is tight enough for what it has to catch: the same
    # gradients from bfloat16 weights part by ten times the tolerance
    rounded = jax.tree_util.tree_map(
        lambda a: a.astype(jnp.bfloat16).astype(F32), params)
    low = jax.jit(jax.grad(lambda p: reference.block_loss(
        p, data, denoms, CONFIG, EINSUM)))(rounded)
    assert max(worst(low[leaf], want[leaf]) for leaf in names) > 10 * TOL


@pytest.mark.parametrize("remat", ["off", "layer"])
def test_the_two_lowerings_of_the_delta_rule_agree_in_the_model(
        remat, monkeypatch):
    """KDA heads of 128 at a tiny hidden size: the loss and every
    parameter's gradient are the same whether ``chunk_kda`` lowers to XLA
    (as the CPU takes it: q and k normalised by heads) or to the kernel pair
    (the test stands in for the TPU and runs Pallas in interpret mode: the
    norm on [b, l, heads d], its sums as products), with the layers kept or
    made again; either way the decay and the gated output norm run on
    [b, l, heads d]. The kernels, forward and backward, lie under the
    ``kda`` scope, and ``gauge/kda/qk_norm.*`` says which layout the norm
    took."""
    from paddle_tpu.ops import linear_attention, remat_policy

    paddle.seed(5)
    model = KimiLinearForCausalLM(program_config(kda_head_dim=128))
    data = batch(seed=1, rows=1, length=96)
    apply = functionalize(model, training=True)
    buffers = {n: b._value for n, b in model.named_buffers()}
    loss_of = remat_policy.apply_policy(
        lambda p: apply(p, buffers, data["ids"], data["labels"])[0], remat)
    step = lambda: jax.jit(jax.value_and_grad(loss_of))  # noqa: E731

    def run():
        get_telemetry().reset()
        lowered = step().lower(get_params(model))
        tiers = sorted(k.split("kda/", 1)[1] for k in get_telemetry().scalars()
                       if k.startswith(("gauge/kda/tier.",
                                        "gauge/kda/qk_norm.")))
        return tiers, lowered.as_text(debug_info=True), \
            step()(get_params(model))

    tiers, _, (want_loss, want) = run()
    assert tiers == ["qk_norm.heads", "tier.xla"]
    monkeypatch.setattr(linear_attention, "_on_tpu", lambda: True)
    monkeypatch.setattr(linear_attention, "_INTERPRET", True)
    tiers, text, (got_loss, got) = run()
    assert tiers == ["qk_norm.flat", "tier.pallas"]
    assert abs(float(got_loss) - float(want_loss)) < 1e-5 * float(want_loss)
    for name in want:
        assert worst(got[name], want[name]) < TOL, name
    # the kernels' operations carry the scope by the rule the benchmark's
    # reader has (``inner_scopes.innermost``), the backward's too
    paths = re.findall(r'loc\("([^"]*chunk_kda_(?:fwd|bwd)/pallas_call)"',
                       text)
    assert {p.rsplit("/", 2)[1] for p in paths} == {"chunk_kda_fwd",
                                                    "chunk_kda_bwd"}
    assert all(inner_scopes.innermost(p) == "kda" for p in paths)
    assert any("transpose(" in p for p in paths)
    get_telemetry().reset()


def test_layer_types_follow_the_published_lists():
    whole = KimiLinearConfig()
    kinds = whole.layer_types
    assert len(kinds) == 27 and kinds[0] == ("kda", "dense")
    assert [i + 1 for i, (m, _) in enumerate(kinds) if m == "mla"] == \
        [4, 8, 12, 16, 20, 24, 27]
    assert all(f == "moe" for _, f in kinds[1:])
    assert program_config().layer_types == reference.layer_kinds(CONFIG)
    with pytest.raises(ValueError, match="neither"):
        KimiLinearConfig(num_hidden_layers=3, kda_layers=(1, 2),
                         full_attn_layers=())


ENTRY = "fleet.train_step"


def build_step(remat="off"):
    paddle.seed(0)
    model, _, _ = model_and_weights()
    opt = paddle.optimizer.Adam(learning_rate=1e-3,
                                parameters=model.parameters(),
                                multi_precision=True)
    mesh = Mesh(np.array(jax.devices()[:1]), ("dp",))
    step = ParallelTrainStep(model, loss_fn=lambda out, lbl: out,
                             optimizer=opt, mesh=mesh, zero_stage=0,
                             remat=remat, compute_dtype=jnp.dtype("bfloat16"))
    data = batch(rows=2, length=64)
    ids, labels = np.asarray(data["ids"]), np.asarray(data["labels"])
    return step, lambda: step((ids, labels), (labels,))


def compiled_ops():
    return hlo_attrib.parse_hlo_text(hlo_attrib.hlo_registry().text_for(ENTRY))


@pytest.fixture(scope="module")
def plain():
    """The step as the cell builds it but for recomputation: four steps'
    losses, the compiled program, what the counters read."""
    get_telemetry().reset()
    step, call = build_step("off")
    losses = [float(call().numpy()) for _ in range(4)]
    step.sync_to_layer()
    got = {"step": step, "losses": losses, "ops": compiled_ops(),
           "compiles": step._jitted.tracker.compiles,
           "kda_calls": get_telemetry().counter_value("kda/calls"),
           "stats": publish_moe_stats(step._layer)}
    get_telemetry().reset()
    return got


def test_trains_through_the_fleet_step(plain):
    step, losses = plain["step"], plain["losses"]
    assert plain["compiles"] == 1
    assert losses[-1] < losses[0]
    # every parameter has its optimizer state where the harness reads it
    assert set(step._opt_state) == set(get_params(step._layer))
    assert all({"moment1", "master"} <= set(s)
               for s in step._opt_state.values())
    assert len(plain["stats"]) == 2
    assert all(s["dropped_pairs"] == 0.0 for s in plain["stats"].values())
    assert plain["kda_calls"] >= 2


def test_layers_made_again_change_the_schedule_not_the_sums(plain):
    get_telemetry().reset()
    step, call = build_step("layer")
    losses = [float(call().numpy()) for _ in range(4)]
    assert step._jitted.tracker.compiles == 1
    # bf16 roundings apart
    np.testing.assert_allclose(losses, plain["losses"], rtol=2e-2)
    # every half of every block is under a checkpoint now (the plain
    # build has chunk_kda's own only)
    remade = lambda ops: sum("checkpoint" in op.op_name  # noqa: E731
                             for op in ops.values())
    assert remade(compiled_ops()) > 2 * remade(plain["ops"])
    step.sync_to_layer()
    assert all(s["dropped_pairs"] == 0.0
               for s in publish_moe_stats(step._layer).values())
    get_telemetry().reset()


def test_compiled_step_names_kda_and_moe_and_changes_no_operation(
        plain, monkeypatch):
    ops = plain["ops"]
    paths = [op.op_name for op in ops.values()]
    for inner, outer in (("kda", "self_attn"), ("moe", "mlp")):
        named = [p for p in paths if f"/{inner}/" in p or f"({inner})" in p
                 or f"/{inner})" in p]
        assert named, inner
        assert any("transpose(" in p for p in named), inner   # the backward
        assert all(hlo_attrib.scope_of(p) == outer for p in named), inner
    # MLA's score space comes with dot_product_attention
    assert set(hlo_attrib.SCOPES) <= {hlo_attrib.scope_of(p) for p in paths}

    get_telemetry().reset()
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    bare_step, bare_call = build_step()
    bare_call()
    bare = compiled_ops()
    assert not any("/kda/" in op.op_name or "/moe/" in op.op_name
                   for op in bare.values())
    assert len(bare) == len(ops)
    assert (collections.Counter(op.opcode for op in bare.values())
            == collections.Counter(op.opcode for op in ops.values()))
    get_telemetry().reset()


def test_tiny_preset_runs_eagerly():
    paddle.seed(1)
    cfg = kimi_linear_tiny(experts_held=range(2, 6), vocab_rows_held=64)
    model = KimiLinearForCausalLM(cfg)
    ids = np.random.default_rng(0).integers(0, 64, (2, 24), dtype=np.int32)
    logits = model(paddle.to_tensor(ids))
    assert tuple(logits.shape) == (2, 24, 64)
    loss = model(paddle.to_tensor(ids), paddle.to_tensor(np.roll(ids, -1, 1)))
    assert np.isfinite(float(loss.numpy()))
