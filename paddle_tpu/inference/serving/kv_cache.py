"""Blocked / paged KV-cache pool — the memory system of token-level serving.

The decode traffic shape (long prompt, streamed decode) keeps per-sequence
state: every generated token attends to every previous token's K/V. A
naive cache reserves ``max_len`` per sequence up front and wastes most of
it (sequences finish early, prompts vary 10-100x); this pool instead
carves one device allocation into fixed-size **blocks** and hands them to
sequences on demand, vLLM-style:

- device side: ``pages['k'] / pages['v']`` hold, a layer, ``num_blocks``
  pages of ``block_size`` tokens (how the layers and the heads lie is the
  **layout**, below: float pages a layer to an array with the heads flat,
  ``[num_blocks, block_size, kv heads * head_dim]``); a token at logical
  position ``p`` of a sequence lives in page
  ``block_table[p // block_size]`` at slot ``p % block_size``. The pages
  pytree flows through the jitted decode step (donated — the pool is the
  single largest serving buffer, it must never exist twice).
- host side: a free list plus an owner map. ``allocate``/``release`` are
  O(blocks moved) and run on the scheduler thread; accounting is exact —
  ``used_blocks`` must return to 0 after a drain, and the decode gate
  fails on a single leaked block.
- **int8 storage** (``dtype='int8'``): K/V quantize on write through
  ``quant.quantize_kv`` (one float32 scale per token-head, stored in
  ``pages['k_scale']/['v_scale']``) and dequantize per page inside the
  attention gather — halving (vs bf16) or quartering (vs f32) the cache's
  HBM so twice the sequences fit before eviction. Accuracy is gated by a
  bf16-reference parity test (tests/test_decode_serving.py).

Page 0 is a reserved **scratch page**: it is never allocated, and every
masked-out write (padding rows of a bucketed batch, padded tail of a
prefill chunk) is redirected into it, so a scatter never needs a
data-dependent guard inside the compiled step.

**Recurrent state** (``state=``): a model whose mixers carry a fixed-size
state between steps (a state-space layer's matrix a head, its convolution's
tail) keeps it in the same pool, one **slot** a sequence beside the pages
that grow with it. ``pages[<leaf>]`` holds, a layer, ``[slots + 1, ...]``;
slot 0 is the scratch slot that padded rows read and write, as page 0 is
for pages. A slot comes with a sequence's first block and goes back in
``release``, so the one terminal funnel and the recompute-style eviction
that free the blocks free the slot too; nothing is cleared on the host:
the compiled step starts a sequence's state from zero at the chunk whose
first position is 0.

**Layout** (``layout=``, named by the model's ``decode_spec``):
``"per_layer"`` keeps a tuple of one array a layer, ``[blocks, block,
kv heads * head_dim]``, the heads flattened into one minor axis that is
whole lanes wide, so that a layer's scatter and gather touch that layer's
array alone and no step slices or relays out the whole pool. Every float
pool is laid out so (Falcon-H1's since PR 35, GPT's since PR 36).
``"stacked"`` keeps K and V as one array each, ``[layers, blocks, block,
kv heads, head_dim]``, and is left for int8 storage alone: its scale a
token-head (``[layers, blocks, block, kv heads]``) wants the heads as an
axis of the pages beside it. A step over a stacked pool copies the whole
pool into a lane-padded layout and back (a head_dim of 64 under 128
lanes) and writes a layer's slice out again around each scatter: 61 ms of
every step of GPT-2 345M over 2,304 blocks (PERF.md, section 6, PR 36); no
cell serves int8, and one layout for both is ROADMAP D10. State leaves are
always a tuple of one array a layer.
``"latent"`` is latent attention's: no K and V pages at all but one leaf,
``pages['latent']``, a tuple of one array a layer ``[blocks, block,
head_dim]`` with ``head_dim`` the cached row's width (the compressed
latent and the rotated shared key side by side, 512 + 64, which the model
pads to whole 128-lane groups: 640), one row a token whatever the heads. It is handed out, released and evicted by the same
blocks as pages of K and V are: the host side does not know the layouts
apart.

**Counters** (``counters=``): small arrays a compiled step adds to and
nothing on the host reads a step (a served expert layer's experts hit and
pairs routed): ``pages[<name>]`` one array each, zero at the start. They
ride in the donated pytree; ``read_counters`` fetches them when asked.
"""
from __future__ import annotations

import threading
from typing import Dict, List, Optional

import jax.numpy as jnp
import numpy as np

from ...profiler.telemetry import get_telemetry

__all__ = ["KVCacheConfig", "KVCachePool", "SCRATCH_PAGE"]

# page 0: the write target for masked-out tokens (see module docstring)
SCRATCH_PAGE = 0

_STORE_DTYPES = ("float32", "bfloat16", "int8")
_LAYOUTS = ("stacked", "per_layer", "latent")


class KVCacheConfig:
    """Geometry + storage dtype of one pool.

    Args:
        num_layers/num_heads/head_dim: the served model's KV shape.
        num_blocks: pool capacity in blocks (one is reserved as scratch).
        block_size: tokens per block — small enough that a finishing
            sequence strands < block_size slots, large enough that the
            per-block gather indices stay cheap (16 is the default
            compromise; vLLM ships the same).
        dtype: 'float32' | 'bfloat16' | 'int8' storage. int8 adds the
            per-token-head scale planes.
        compute_dtype: dtype K/V are dequantized to for the attention
            dot (defaults to float32 off-int8 storage dtype).
        num_kv_heads: heads of K and V where they are fewer than the
            query heads (grouped queries); the pages hold these.
        layout: 'stacked' | 'per_layer' | 'latent' (module docstring).
        state: recurrent-state leaves, ``{name: (shape a slot and layer,
            dtype)}``, or None; ``state_slots`` sequences can hold one.
        counters: ``{name: (shape, dtype)}`` of arrays the compiled steps
            add to, or None.
    """

    def __init__(self, num_layers: int, num_heads: int, head_dim: int,
                 num_blocks: int = 64, block_size: int = 16,
                 dtype: str = "float32",
                 compute_dtype: Optional[str] = None,
                 num_kv_heads: Optional[int] = None,
                 layout: str = "stacked",
                 state: Optional[Dict[str, tuple]] = None,
                 state_slots: int = 0,
                 counters: Optional[Dict[str, tuple]] = None):
        if dtype not in _STORE_DTYPES:
            raise ValueError(f"kv dtype {dtype!r} not in {_STORE_DTYPES}")
        if num_blocks < 2:
            raise ValueError("num_blocks must be >= 2 (page 0 is scratch)")
        if layout not in _LAYOUTS:
            raise ValueError(f"kv layout {layout!r} not in {_LAYOUTS}")
        if layout != "stacked" and dtype == "int8":
            raise ValueError(f"the {layout} layout flattens the heads; int8 "
                             "pages keep a scale a head and stay 'stacked'")
        if state and state_slots < 1:
            raise ValueError("a recurrent-state pool needs state_slots >= 1")
        self.num_layers = int(num_layers)
        self.num_heads = int(num_heads)
        self.num_kv_heads = int(num_kv_heads or num_heads)
        self.layout = layout
        self.state = dict(state or {})
        self.state_slots = int(state_slots) if self.state else 0
        self.counters = dict(counters or {})
        self.head_dim = int(head_dim)
        self.num_blocks = int(num_blocks)
        self.block_size = int(block_size)
        self.dtype = dtype
        self.compute_dtype = compute_dtype or (
            "float32" if dtype == "int8" else dtype)

    @property
    def usable_blocks(self) -> int:
        return self.num_blocks - 1  # minus the scratch page

    def max_tokens(self) -> int:
        return self.usable_blocks * self.block_size

    def blocks_for(self, n_tokens: int) -> int:
        return -(-int(n_tokens) // self.block_size)


class KVCachePool:
    """One device pool + its host-side block accounting."""

    def __init__(self, config: KVCacheConfig):
        self.config = config
        c = config
        store = jnp.int8 if c.dtype == "int8" else jnp.dtype(c.dtype)
        if c.layout in ("per_layer", "latent"):
            shape = (c.num_blocks, c.block_size, c.num_kv_heads * c.head_dim)
            leaves = ("latent",) if c.layout == "latent" else ("k", "v")
            self.pages: Dict[str, object] = {
                kv: tuple(jnp.zeros(shape, store)
                          for _ in range(c.num_layers)) for kv in leaves}
        else:
            shape = (c.num_layers, c.num_blocks, c.block_size,
                     c.num_kv_heads, c.head_dim)
            self.pages = {"k": jnp.zeros(shape, store),
                          "v": jnp.zeros(shape, store)}
        if c.dtype == "int8":
            sshape = shape[:-1]  # [L, N, bs, H] — one scale per token-head
            self.pages["k_scale"] = jnp.zeros(sshape, jnp.float32)
            self.pages["v_scale"] = jnp.zeros(sshape, jnp.float32)
        for name, (slot_shape, dtype) in c.state.items():
            if name in self.pages:
                raise ValueError(f"state leaf {name!r} is a page leaf's name")
            self.pages[name] = tuple(
                jnp.zeros((c.state_slots + 1, *slot_shape), jnp.dtype(dtype))
                for _ in range(c.num_layers))
        for name, (shape_, dtype) in c.counters.items():
            if name in self.pages:
                raise ValueError(f"counter {name!r} is another leaf's name")
            self.pages[name] = jnp.zeros(shape_, jnp.dtype(dtype))
        self._lock = threading.Lock()
        self._free: List[int] = list(range(1, c.num_blocks))
        self._owned: Dict[int, List[int]] = {}  # request id -> block ids
        # slot 0 is scratch; a pool without state hands out none
        self._free_slots: List[int] = list(range(c.state_slots, 0, -1))
        self._slot_of: Dict[int, int] = {}      # request id -> state slot
        self._tel = get_telemetry()
        if self._tel.enabled:
            self._tel.gauge("serve/kv_blocks_total", c.usable_blocks)
            if c.state:
                self._tel.gauge("serve/state_slots_total", c.state_slots)
            self._publish_locked()

    # -- accounting (host, scheduler thread + the engine's finish funnel) --
    def _publish_locked(self) -> None:
        if not self._tel.enabled:
            return
        used = self.config.usable_blocks - len(self._free)
        self._tel.gauge("serve/kv_blocks_used", used)
        self._tel.gauge("serve/kv_occupancy",
                        used / max(self.config.usable_blocks, 1))
        if self.config.state:
            slots = self.config.state_slots
            self._tel.gauge("serve/state_slots_used",
                            slots - len(self._free_slots))
            self._tel.gauge("serve/state_occupancy",
                            (slots - len(self._free_slots)) / slots)

    def ensure(self, owner: int, n_tokens: int) -> bool:
        """Grow ``owner``'s block list to cover ``n_tokens`` positions;
        with its first block a sequence takes its state slot, where the
        pool holds state. Returns False (allocating NOTHING — no partial
        grabs to unwind) when the free list cannot cover the growth or no
        slot is free; the scheduler then evicts or defers."""
        need = self.config.blocks_for(n_tokens)
        with self._lock:
            have = self._owned.setdefault(owner, [])
            grow = need - len(have)
            if grow <= 0:
                return True
            if grow > len(self._free):
                return False
            if self.config.state and owner not in self._slot_of:
                if not self._free_slots:
                    return False
                self._slot_of[owner] = self._free_slots.pop()
            taken = [self._free.pop() for _ in range(grow)]
            have.extend(taken)
            if self._tel.enabled:
                self._tel.counter("serve/kv_blocks_alloc", len(taken))
            self._publish_locked()
            return True

    def release(self, owner: int) -> int:
        """Return every block of ``owner``, and its state slot, to the
        free lists (idempotent — the engine's terminal funnel calls it for
        every request, whether or not it ever owned cache). Returns the
        number of blocks freed."""
        with self._lock:
            blocks = self._owned.pop(owner, None) or []
            slot = self._slot_of.pop(owner, None)
            if not blocks and slot is None:
                return 0
            if slot is not None:
                self._free_slots.append(slot)
            if blocks:
                self._free.extend(blocks)
                if self._tel.enabled:
                    self._tel.counter("serve/kv_blocks_free", len(blocks))
            self._publish_locked()
            return len(blocks)

    def owned(self, owner: int) -> List[int]:
        with self._lock:
            return list(self._owned.get(owner, ()))

    @property
    def used_blocks(self) -> int:
        with self._lock:
            return self.config.usable_blocks - len(self._free)

    @property
    def free_blocks(self) -> int:
        with self._lock:
            return len(self._free)

    def occupancy(self) -> float:
        return self.used_blocks / max(self.config.usable_blocks, 1)

    def state_occupancy(self) -> float:
        """Share of the state slots that sequences hold; 0.0 for a pool
        without recurrent state."""
        with self._lock:
            return ((self.config.state_slots - len(self._free_slots))
                    / max(self.config.state_slots, 1))

    def slot(self, owner: int) -> int:
        """``owner``'s state slot; the scratch slot 0 where it holds none
        (a pool without state, a sequence without cache)."""
        with self._lock:
            return self._slot_of.get(owner, SCRATCH_PAGE)

    def accounting(self) -> dict:
        """The leak ledger: after a drain, ``leaked_blocks`` (and, where
        the pool holds recurrent state, ``leaked_slots``) must be 0 and
        ``owners`` empty — the decode gate and the drain test assert it."""
        with self._lock:
            used = self.config.usable_blocks - len(self._free)
            out = {"total_blocks": self.config.usable_blocks,
                   "used_blocks": used,
                   "leaked_blocks": used,
                   "owners": sorted(self._owned)}
            if self.config.state:
                held = self.config.state_slots - len(self._free_slots)
                out.update(total_slots=self.config.state_slots,
                           used_slots=held, leaked_slots=held,
                           slot_owners=sorted(self._slot_of))
            return out

    def read_counters(self) -> dict:
        """The counters' values now, as numpy arrays: the caller's fetch
        (it waits for the last step dispatched). Empty once the pages are
        gone."""
        if not self.pages:
            return {}
        return {name: np.asarray(self.pages[name])
                for name in self.config.counters}

    # -- device-facing helpers ---------------------------------------------
    def block_table(self, owner: int, width: int) -> np.ndarray:
        """``owner``'s page ids padded to ``width`` with the scratch page
        (padding is never dereferenced — masked by kv_lens/q_positions)."""
        blocks = self.owned(owner)
        if len(blocks) > width:
            raise ValueError(f"owner {owner} holds {len(blocks)} blocks, "
                             f"table width is {width}")
        out = np.full(width, SCRATCH_PAGE, np.int32)
        out[:len(blocks)] = blocks
        return out

    def table_width(self, max_tokens: int) -> int:
        return self.config.blocks_for(max_tokens)
