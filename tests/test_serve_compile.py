"""GPT's served step through the TPU v5e's own compiler, at the widths and
the pool of ``gpt2-345m.serve-closed-decode`` (1,024 wide, 16 heads, 2,304
blocks of 16 in bfloat16, a table of 64 slots) cut to two layers: a decode
step of 64 rows and a prefill chunk of 256 tokens, under each paged tier.

What is read is the optimised HLO: outside the in-place scatter nothing may
write a whole layer of the pool or more. The stacked five-axis pool did (K
and V of all layers copied into a padded layout and back, a layer's slice
written out again around its scatter: 61 ms of every step, PERF.md section
6, PR 36); pages that lie a layer to an array with the heads flat do not.
Nothing runs: the chip is described, not attached, and a compile that
passes is not a chip run.

The topology is described inside a fixture, never at import (only one
process may load the TPU's library; ``tests/test_kda_tpu_compile.py`` is
the other file that does so, and a worker given both loads it once).
"""
import math
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

import paddle_tpu as paddle
from paddle_tpu.inference.serving.decode import _pool_config, greedy_step
from paddle_tpu.inference.serving.kv_cache import KVCachePool
from paddle_tpu.jit.functionalize import get_params
from paddle_tpu.text.models.gpt import GPTConfig, GPTForCausalLM

LAYERS, WIDTH, HEADS, BLOCKS, BLOCK, TABLE = 2, 1024, 16, 2304, 16, 64
LAYER_OF_THE_POOL = BLOCKS * BLOCK * WIDTH  # elements of K (or V) a layer

# instructions that hand a buffer on and write nothing
_PASSES_ON = {"parameter", "get-tuple-element", "bitcast", "tuple", "while"}


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def compile_for_the_chip(fn, *args, **kw):
    # an executable for a described chip cannot be read back from the
    # persistent cache without one: keep it out
    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        return jax.jit(fn, **kw).lower(*args).compile()
    finally:
        jax.config.update("jax_enable_compilation_cache", before)


def instructions(text):
    """(computation, name, result type, opcode, rest of the line, whether
    it is its computation's root) of every instruction of an HLO module's
    text."""
    comp = None
    for line in text.splitlines():
        head = re.match(r"(?:ENTRY )?%(\S+) \(.*\) -> .* \{$", line)
        if head:
            comp = head.group(1)
            continue
        m = re.match(r"\s+(ROOT )?%(\S+) = ", line)
        if not m:
            continue
        rest = line[m.end():]
        if rest.startswith("("):  # a tuple's type: to its closing bracket
            depth, end = 0, 0
            for end, ch in enumerate(rest):
                depth += (ch == "(") - (ch == ")")
                if depth == 0:
                    break
            typ, rest = rest[:end + 1], rest[end + 2:]
        else:
            typ, _, rest = rest.partition(" ")
        yield comp, m.group(2), typ, rest.partition("(")[0], rest, \
            bool(m.group(1))


def pool_sized_writes(text, blocks=BLOCKS, layer=LAYER_OF_THE_POOL):
    """Instructions whose result holds a bfloat16 array over all the
    pool's blocks, a layer of it or more, and that are neither the scatter
    nor a fusion around it (both update the donated pages where they
    lie)."""
    instrs = list(instructions(text))
    root_op = {comp: op for comp, _, _, op, _, root in instrs if root}
    found = []
    for comp, name, typ, op, rest, _ in instrs:
        shapes = [[int(d) for d in dims.split(",") if d]
                  for dims in re.findall(r"bf16\[([0-9,]*)\]", typ)]
        if op in _PASSES_ON or not any(
                blocks in shape and math.prod(shape) >= layer
                for shape in shapes):
            continue
        if op == "scatter":
            continue
        calls = re.search(r"calls=%(\S+?)[,\s]", rest)
        if op == "fusion" and calls and root_op.get(calls.group(1)) \
                == "scatter":
            continue
        found.append(f"{comp}: %{name} = {typ} {op}")
    return found


def test_the_reader_finds_what_the_stacked_pool_compiled_to():
    """The HLO lines of the stacked layout's step (the parent of PR 36, the
    same compiler): the reader must name each, and pass the scatter."""
    text = """
%fused_computation.7 (param_0.21: bf16[2304,16,1024], param_1.26: s32[64], param_2.16: bf16[64,1024]) -> bf16[2304,16,1024] {
  %param_0.21 = bf16[2304,16,1024]{2,1,0:T(8,128)(2,1)} parameter(0)
  ROOT %scatter.9 = bf16[2304,16,1024]{2,1,0:T(8,128)(2,1)} scatter(%param_0.21, %custom-call.3, %transpose.147), update_window_dims={1}
}

%fused_computation.9 (param_0.2: bf16[24,2304,16,16,64]) -> bf16[2304,16,16,64] {
  %param_0.2 = bf16[24,2304,16,16,64]{4,3,2,1,0:T(8,128)(2,1)} parameter(0)
  ROOT %dynamic-slice.1 = bf16[2304,16,16,64]{3,2,1,0:T(8,128)(2,1)} dynamic-slice(%param_0.2), dynamic_slice_sizes={1,2304,16,16,64}
}

ENTRY %main.35 (cache.1: bf16[24,2304,16,16,64]) -> (s32[64,1], bf16[2304,16,1024]) {
  %cache.1 = bf16[24,2304,16,16,64]{4,3,2,1,0:T(8,128)(2,1)} parameter(0)
  %copy.4 = bf16[24,2304,16,16,64]{1,4,3,2,0:T(8,128)(2,1)} copy(%cache.1)
  %fusion.9 = bf16[2304,16,16,64]{3,2,1,0:T(8,128)(2,1)} fusion(%copy.4), kind=kLoop, calls=%fused_computation.9
  %fusion.7 = bf16[2304,16,1024]{2,1,0:T(8,128)(2,1)} fusion(%k.1, %fusion.140, %gte.421), kind=kCustom, calls=%fused_computation.7, metadata={op_name="jit(step)/scatter"}
  %while.4 = (u32[]{:T(128)}, bf16[2304,16,1024]{2,1,0:T(8,128)(2,1)}) while(%tuple.128), condition=%cond, body=%body
  ROOT %tuple.9 = (s32[64,1]{1,0}, bf16[2304,16,1024]{2,1,0:T(8,128)(2,1)}) tuple(%argmax, %fusion.7)
}
"""
    found = pool_sized_writes(text)
    assert [f.split(" = ")[0] for f in found] == [
        "fused_computation.9: %dynamic-slice.1", "main.35: %copy.4",
        "main.35: %fusion.9"], found


@pytest.mark.parametrize("tier", ["paged_scan", "paged_gather"])
@pytest.mark.parametrize("entry, rows, tokens", [
    ("serve.decode.b64", 64, 1), ("serve.prefill.c256", 1, 256)])
def test_a_served_step_writes_no_whole_layer_of_the_pool(
        one_chip, monkeypatch, tier, entry, rows, tokens):
    monkeypatch.setenv("PADDLE_TPU_ATTN_PAGED_POLICY", tier)
    shaped = lambda shape, dt: jax.ShapeDtypeStruct(  # noqa: E731
        shape, dt, sharding=one_chip)
    with paddle.LazyGuard():  # shapes only: the weights are never made
        model = GPTForCausalLM(GPTConfig(
            vocab_size=50304, hidden_size=WIDTH, num_layers=LAYERS,
            num_heads=HEADS, max_position_embeddings=TABLE * BLOCK,
            hidden_dropout=0.0, attention_dropout=0.0))
    spec = model.decode_spec("bfloat16")
    params = {name: shaped(p.shape, jnp.bfloat16)
              for name, p in get_params(model).items()}
    pool = _pool_config(spec, BLOCKS, BLOCK, "bfloat16", 0)
    pages = jax.tree_util.tree_map(
        lambda a: shaped(a.shape, a.dtype),
        jax.eval_shape(lambda: KVCachePool(pool).pages))
    assert [a.shape for a in pages["k"]] == [(BLOCKS, BLOCK, WIDTH)] * LAYERS

    # the scheduler's own entry: the token store read and written with it
    ints = lambda *shape: shaped(shape, jnp.int32)  # noqa: E731
    compiled = compile_for_the_chip(
        greedy_step(spec["forward_chunk"], store=True), params,
        ints(rows, tokens), ints(rows, tokens), pages, ints(rows, TABLE),
        ints(rows), ints(rows), ints(BLOCKS), donate_argnums=(3, 7))
    text = compiled.as_text()
    assert "scatter" in text, "the reader would pass an empty module"
    found = pool_sized_writes(text)
    assert not found, f"{entry} under {tier}:\n" + "\n".join(found)
    assert "remat_" not in text
    if tier == "paged_scan":
        # nor does a decode step's scan lay its gathered page out anew
        # with the heads as an axis (64 under 128 lanes: 4.8 us a page of
        # 64 rows, twice an iteration, on the chip): it reads it a lane
        # group at a time. (A prefill chunk's page is one row's, and is.)
        assert tokens > 1 or not re.search(
            rf"= (f32|bf16)\[{rows},{BLOCK},{HEADS},{WIDTH // HEADS}\]",
            text)
        # the donated pages are the step's output; beside them the scan
        # keeps a page a row, far under one layer's K (75 MB). The stacked
        # pool's step held 11 GB. (paged_gather widens every row's whole
        # table to float32, 0.7 GB at 64 rows: the table's, not the pool's)
        assert compiled.memory_analysis().temp_size_in_bytes \
            < 2 * LAYER_OF_THE_POOL


# -- the latent pool of a served latent-attention expert model ------------------

@pytest.mark.parametrize("entry, rows, tokens", [
    ("serve.decode.b64", 64, 1), ("serve.prefill.c512", 1, 512)])
def test_a_latent_step_copies_neither_its_pool_nor_its_weights(
        one_chip, entry, rows, tokens):
    """openPangu-Ultra-MoE's served step at the widths and the pool of
    ``openpangu-ultra-moe-718b.serve-closed-reason`` (7,680 wide, 128
    heads, 16 of 256 experts, 16,384 blocks of 16 rows stored 640 wide, a
    table of 320 slots), cut to one dense and one expert layer. With rows
    576 wide the runtime laid the blocks along the lanes and every step
    copied each layer's array into the row-major layout and back; without
    the barrier after W_qb's product the compiler laid that weight out anew
    a step (PERF.md section 6, PR 37)."""
    from paddle_tpu.text.models.pangu_ultra_moe import (
        PanguUltraMoEConfig, PanguUltraMoEForCausalLM)

    blocks, table = 16384, 320
    shaped = lambda shape, dt: jax.ShapeDtypeStruct(  # noqa: E731
        shape, dt, sharding=one_chip)
    with paddle.LazyGuard():  # shapes only: the weights are never made
        model = PanguUltraMoEForCausalLM(PanguUltraMoEConfig(
            vocab_rows_held=19200, layers_held=2, dense_layers_held=1,
            experts_held=range(16), nextn_held=0))
    spec = model.decode_spec("bfloat16")
    assert spec["head_dim"] == 640
    params = {name: shaped(p.shape, jnp.bfloat16)
              for name, p in get_params(model).items()}
    pool = _pool_config(spec, blocks, BLOCK, "bfloat16", 0)
    pages = jax.tree_util.tree_map(
        lambda a: shaped(a.shape, a.dtype),
        jax.eval_shape(lambda: KVCachePool(pool).pages))
    assert [a.shape for a in pages["latent"]] == [(blocks, BLOCK, 640)] * 2

    ints = lambda *shape: shaped(shape, jnp.int32)  # noqa: E731
    compiled = compile_for_the_chip(
        greedy_step(spec["forward_chunk"], store=True), params,
        ints(rows, tokens), ints(rows, tokens), pages, ints(rows, table),
        ints(rows), ints(rows), ints(blocks), donate_argnums=(3, 7))
    text = compiled.as_text()
    assert "scatter" in text, "the reader would pass an empty module"
    found = pool_sized_writes(text, blocks, blocks * BLOCK * 640)
    assert not found, f"{entry}:\n" + "\n".join(found)
    # W_qb (1,536 x 128 heads of 192) is read where it lies
    assert not re.search(r"= bf16\[128,192,1536\]", text)
    assert not re.search(r"= bf16\[1536,24576\]\S* copy\(", text)
    # beside the donated pages a step keeps a group of gathered rows a
    # sequence and a chunk's scores: far under one layer of the pool
    assert compiled.memory_analysis().temp_size_in_bytes \
        < blocks * BLOCK * 640 * 2


# -- the held experts of a served step: batched at a decode step's rows ---------

def outermost_calls(text):
    """Name of a fused computation -> the line of the instruction that
    calls it from outside every fusion: the one a trace's event is named
    by, and whose ``op_name`` says its scope."""
    instrs = list(instructions(text))
    caller = {}
    for comp, _, _, _, rest, _ in instrs:
        for called in re.findall(r"calls=%([^,\s]+)", rest):
            caller[called] = (comp, rest)
    out = {}
    for called in caller:
        comp, rest = caller[called]
        while comp in caller:
            comp, rest = caller[comp]
        out[called] = rest
    return out


@pytest.mark.parametrize("model, entry, rows, tokens", [
    ("openpangu", "serve.decode.b64", 64, 1),
    ("openpangu", "serve.prefill.c512", 1, 512),
    ("longcat", "serve.decode.b128", 128, 1),
    ("longcat", "serve.prefill.c512", 1, 512)])
def test_a_decode_steps_held_experts_are_one_batched_product_a_projection(
        one_chip, model, entry, rows, tokens):
    """The expert layer of ``openpangu-ultra-moe-718b.serve-closed-reason``
    (7,680 wide, 16 held stacks of 2,048) and of
    ``longcat-flash-chat.serve-closed-chat`` (6,144 wide, 16 of 2,048),
    one expert layer each. At a decode step's 64 or 128 rows the held
    experts run as three batched products over the 16 stacks, each stack
    read where it lies: no grouped product (``ragged-dot``, a 512-row tile
    a group) and no copy of a stack. Their fusions' ``op_name`` lies under
    ``moe/``, so the benchmark's ``moe_decode_ms.serve`` books them. A
    prefill chunk of 512 keeps the grouped products."""
    shaped = lambda shape, dt: jax.ShapeDtypeStruct(  # noqa: E731
        shape, dt, sharding=one_chip)
    blocks = 16384
    with paddle.LazyGuard():  # shapes only: the weights are never made
        if model == "openpangu":
            from paddle_tpu.text.models.pangu_ultra_moe import (
                PanguUltraMoEConfig, PanguUltraMoEForCausalLM)

            net = PanguUltraMoEForCausalLM(PanguUltraMoEConfig(
                vocab_rows_held=19200, layers_held=2, dense_layers_held=1,
                experts_held=range(16), nextn_held=0))
            h, table = 7680, 320
        else:
            from paddle_tpu.text.models.longcat_flash import (
                LongcatFlashConfig, LongcatFlashForCausalLM)

            net = LongcatFlashForCausalLM(LongcatFlashConfig(
                vocab_rows_held=16384, layers_held=1, experts_held=range(16)))
            h, table = 6144, 192
    spec = net.decode_spec("bfloat16")
    params = {name: shaped(p.shape, jnp.bfloat16)
              for name, p in get_params(net).items()}
    stacks = {name: p.shape for name, p in params.items()
              if re.search(r"w_(gate|up|down)$", name)}
    assert sorted(set(stacks.values())) == [(16, 2048, h), (16, h, 2048)]
    pool = _pool_config(spec, blocks, BLOCK, "bfloat16", 0)
    pages = jax.tree_util.tree_map(
        lambda a: shaped(a.shape, a.dtype),
        jax.eval_shape(lambda: KVCachePool(pool).pages))

    ints = lambda *shape: shaped(shape, jnp.int32)  # noqa: E731
    text = compile_for_the_chip(
        greedy_step(spec["forward_chunk"], store=True), params,
        ints(rows, tokens), ints(rows, tokens), pages, ints(rows, table),
        ints(rows), ints(rows), ints(blocks),
        donate_argnums=(3, 7)).as_text()
    if tokens > 1:
        assert "ragged-dot" in text, entry
        return
    assert "ragged-dot" not in text, entry
    copies = [f"%{name} = {typ}" for _, name, typ, op, _, _ in
              instructions(text) if op == "copy"
              and re.match(rf"bf16\[16,({h},2048|2048,{h})\]", typ)]
    assert not copies, "\n".join(copies)
    # the three products: [16, rows, h] x [16, h, 2048] twice, and
    # [16, rows, 2048] x [16, 2048, h]
    products = [(comp, rest) for comp, _, typ, op, rest, _ in
                instructions(text) if op in ("convolution", "dot")
                and re.match(rf"bf16\[16,{rows},(2048|{h})\]", typ)]
    assert len(products) == 3, products
    calls = outermost_calls(text)
    for comp, rest in products:
        named = calls.get(comp, rest)
        assert re.search(r'op_name="[^"]*/moe/', named), named


# -- LongCat's decode step: the latent walks of rows grouped by length ----------

@pytest.mark.parametrize("entry, rows, tokens, walks", [
    ("serve.decode.b128", 128, 1, 8 * 4), ("serve.prefill.c512", 1, 512, 8)])
def test_longcats_rows_walk_the_latent_pages_in_groups_by_length(
        one_chip, entry, rows, tokens, walks):
    """LongCat-Flash's served step at the size of
    ``longcat-flash-chat.serve-closed-chat`` (6,144 wide, 64 heads, 16 of
    512 experts, four double layers of two attentions, 16,384 blocks of 16
    rows stored 640 wide, a table of 192 slots). A decode step of 128 rows
    walks each attention's pages in four groups of 32 rows sorted by
    length (``longcat_flash.WALK_ROWS``), each only as far as its own
    longest row: 32 loops, where one walk of all the rows an attention
    made 8 and a step cost as much as its one longest row asked. A prefill
    chunk of one row walks once. Nothing writes a layer of the pool outside
    its scatter."""
    from paddle_tpu.text.models.longcat_flash import (
        WALK_ROWS, LongcatFlashConfig, LongcatFlashForCausalLM)

    assert WALK_ROWS == 32
    blocks, table = 16384, 192
    shaped = lambda shape, dt: jax.ShapeDtypeStruct(  # noqa: E731
        shape, dt, sharding=one_chip)
    with paddle.LazyGuard():  # shapes only: the weights are never made
        model = LongcatFlashForCausalLM(LongcatFlashConfig(
            vocab_rows_held=16384, layers_held=4, experts_held=range(16)))
    spec = model.decode_spec("bfloat16")
    params = {name: shaped(p.shape, jnp.bfloat16)
              for name, p in get_params(model).items()}
    pool = _pool_config(spec, blocks, BLOCK, "bfloat16", 0)
    pages = jax.tree_util.tree_map(
        lambda a: shaped(a.shape, a.dtype),
        jax.eval_shape(lambda: KVCachePool(pool).pages))
    assert [a.shape for a in pages["latent"]] == [(blocks, BLOCK, 640)] * 8

    ints = lambda *shape: shaped(shape, jnp.int32)  # noqa: E731
    compiled = compile_for_the_chip(
        greedy_step(spec["forward_chunk"], store=True), params,
        ints(rows, tokens), ints(rows, tokens), pages, ints(rows, table),
        ints(rows), ints(rows), ints(blocks), donate_argnums=(3, 7))
    text = compiled.as_text()
    found = [op for *_, op, _, _ in instructions(text) if op == "while"]
    assert len(found) == walks, entry
    found = pool_sized_writes(text, blocks, blocks * BLOCK * 640)
    assert not found, f"{entry}:\n" + "\n".join(found)
    assert compiled.memory_analysis().temp_size_in_bytes \
        < blocks * BLOCK * 640 * 2


# -- the token store beside a hybrid model's gathered contexts -------------------

def test_the_token_store_leaves_the_decode_step_its_fast_memory(
        one_chip, monkeypatch):
    """Falcon-H1's served decode step at the configuration, pool (4,096
    blocks of 16, a table of 96 slots, 64 state slots) and paged tier of
    ``falcon-h1-34b.serve-closed-chat``, all nine layers (where the v5e's
    compiler puts a buffer is the whole program's choice), with the token
    store and without. The store is 16 KB, and so is what it may add to the
    step's temporaries: where the step took its emitted token by an index
    from ``kv_lens``, the compiler kept two of the 100 MB gathered contexts
    out of its fast memory, 97 MB more temporaries and 2.9 ms more a step on
    the chip (PERF.md section 6)."""
    import json

    from benchmark.families.falcon_h1_serve import build_model

    monkeypatch.setenv("PADDLE_TPU_ATTN_PAGED_POLICY", "paged_gather")
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmark", "configs",
                           "falcon-h1-34b.json")) as f:
        model = build_model(json.load(f), {})  # shapes only: no weights
    spec = model.decode_spec("bfloat16")
    blocks, table, rows = 4096, 96, 64
    shaped = lambda shape, dt: jax.ShapeDtypeStruct(  # noqa: E731
        shape, dt, sharding=one_chip)
    ints = lambda *shape: shaped(shape, jnp.int32)  # noqa: E731
    params = {name: shaped(p.shape, jnp.bfloat16)
              for name, p in get_params(model).items()}
    pool = _pool_config(spec, blocks, BLOCK, "bfloat16", rows)
    pages = jax.tree_util.tree_map(
        lambda a: shaped(a.shape, a.dtype),
        jax.eval_shape(lambda: KVCachePool(pool).pages))
    feed = (params, ints(rows, 1), ints(rows, 1), pages, ints(rows, table),
            ints(rows), ints(rows))
    plain = compile_for_the_chip(
        greedy_step(spec["forward_chunk"]), *feed, donate_argnums=(3,))
    stored = compile_for_the_chip(
        greedy_step(spec["forward_chunk"], store=True), *feed, ints(blocks),
        donate_argnums=(3, 7))
    temps = [c.memory_analysis().temp_size_in_bytes for c in (plain, stored)]
    assert temps[0] > 50 * 2**20, "the step gathers no context: " \
        f"{temps}"
    assert temps[1] - temps[0] < 2**20, temps
