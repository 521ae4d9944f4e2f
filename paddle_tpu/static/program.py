"""Program: the declarative-graph facade.

Parity with the reference's ProgramDesc + python Program/Block API
(framework/framework.proto:202, python/paddle/fluid/framework.py:4301) —
re-designed for XLA: while the guard is active, every eager op *also* records
(fn, inputs, outputs) into the Program's op list (an SSA trace). At
``Executor.run`` the trace replays as a pure function of (feeds, params) and
compiles with jax.jit — so the reference's per-op executor interpretation
loop (framework/executor.cc:292) becomes a single compiled XLA program, and
all 109 IR fusion/memory passes are subsumed by the XLA pipeline.
"""
from __future__ import annotations

import contextlib
import threading
from typing import Any, Callable, Dict, List, Optional

import numpy as np

from ..core import tensor as tensor_mod
from ..core.tensor import Parameter, Tensor

__all__ = [
    "Program", "program_guard", "default_main_program", "default_startup_program",
    "data", "InputSpec", "name_scope",
]


class OpRecord:
    __slots__ = ("fn", "args", "out_ids", "multi_out", "name", "amp")

    def __init__(self, fn, args, out_ids, multi_out, name="", amp=None):
        self.fn = fn
        self.args = args  # mix of ("var", id) refs and raw constants
        self.out_ids = out_ids
        self.multi_out = multi_out
        self.name = name
        # amp state SNAPSHOT at record time (dtype, level, white, black) —
        # ops recorded inside paddle.amp.auto_cast must replay with the
        # same casts even though replay happens outside the context (the
        # reference bakes AMP into the program via the
        # mixed_precision.decorate rewrite pass; recording the ambient
        # state achieves the same program-carries-its-AMP property)
        self.amp = amp


class Program:
    def __init__(self):
        self.ops: List[OpRecord] = []
        self.feed_vars: Dict[str, Tensor] = {}
        self.vars_by_name: Dict[str, Tensor] = {}
        self.parameters: Dict[int, Parameter] = {}
        self._var_refs: Dict[int, Tensor] = {}  # keep placeholders alive
        self._optimize = None  # (optimizer, loss_tensor)
        self._grad_map: Dict[int, Tensor] = {}  # param id -> grad placeholder
        self.random_seed = 0
        self._appended_backward = False
        self.declared_shapes: Dict[str, list] = {}  # feed name -> user shape
        # persistent-var updates that ride the training step (reference:
        # ops like data_norm emit summary-update outputs the optimizer
        # applies each step): param id -> id of the recorded op output
        # holding its post-step value. The executor commits these after
        # every optimized run.
        self.buffer_updates: Dict[int, int] = {}

    # ------------------------------------------------------------- recording
    def record_op(self, fn, args, outs, multi_out, name=""):
        ref_args = []
        for a in args:
            if isinstance(a, Tensor):
                self._var_refs[id(a)] = a
                if isinstance(a, Parameter):
                    self.parameters[id(a)] = a
                ref_args.append(("var", id(a)))
            else:
                ref_args.append(("const", a))
        out_ids = []
        for o in outs:
            self._var_refs[id(o)] = o
            out_ids.append(id(o))
        from ..amp.auto_cast import amp_state

        st = amp_state()
        amp = ((st.dtype, st.level, tuple(st.custom_white),
                tuple(st.custom_black)) if st.enabled else None)
        self.ops.append(OpRecord(fn, ref_args, out_ids, multi_out, name, amp))

    def add_feed_var(self, name, t: Tensor):
        self.feed_vars[name] = t
        self.vars_by_name[name] = t
        self._var_refs[id(t)] = t

    # ------------------------------------------------------------- replay
    def build_replay(self):
        """Returns pure fn(feed_dict_raw, params_raw_by_uid) -> env dict."""
        ops = list(self.ops)
        feed_ids = {name: id(t) for name, t in self.feed_vars.items()}
        param_ids = list(self.parameters.keys())

        def replay(feed_raw: Dict[str, Any], params_raw: Dict[int, Any]):
            env: Dict[int, Any] = {}
            for name, uid in feed_ids.items():
                env[uid] = feed_raw[name]
            for uid in param_ids:
                env[uid] = params_raw[uid]

            def resolve(ref):
                kind, v = ref
                if kind == "const":
                    return v
                if v in env:
                    return env[v]
                # non-feed, non-param external tensor (e.g. buffer): use its
                # recorded concrete value
                return self._var_refs[v]._value

            from ..amp.auto_cast import auto_cast

            for op in ops:
                vals = [resolve(r) for r in op.args]
                if op.amp is not None:
                    dt, level, white, black = op.amp
                    with auto_cast(True, custom_white_list=white,
                                   custom_black_list=black, level=level,
                                   dtype=dt):
                        out = op.fn(*vals)
                else:
                    out = op.fn(*vals)
                if op.multi_out:
                    for uid, o in zip(op.out_ids, out):
                        env[uid] = o
                else:
                    env[op.out_ids[0]] = out
            return env

        return replay

    # ------------------------------------------------------------- paddle API
    def global_block(self):
        return _BlockFacade(self)

    def clone(self, for_test=False):
        import copy

        p = Program()
        p.ops = list(self.ops)
        p.feed_vars = dict(self.feed_vars)
        p.vars_by_name = dict(self.vars_by_name)
        p.parameters = dict(self.parameters)
        p._var_refs = dict(self._var_refs)
        p._optimize = None if for_test else self._optimize
        p.declared_shapes = dict(self.declared_shapes)
        p.buffer_updates = {} if for_test else dict(self.buffer_updates)
        return p

    def all_parameters(self):
        return list(self.parameters.values())

    def list_vars(self):
        return list(self._var_refs.values())

    def __repr__(self):
        return (
            f"Program(ops={len(self.ops)}, feeds={list(self.feed_vars)}, "
            f"params={len(self.parameters)})"
        )


class _BlockFacade:
    """Enough of Block's surface for common user code (framework.py:2814)."""

    def __init__(self, program):
        self.program = program

    @property
    def ops(self):
        return self.program.ops

    def var(self, name):
        return self.program.vars_by_name[name]

    def all_parameters(self):
        return self.program.all_parameters()


class _State(threading.local):
    def __init__(self):
        self.main: Optional[Program] = None
        self.startup: Optional[Program] = None
        self.static_mode = False


_state = _State()
_default_main = Program()
_default_startup = Program()


def _enable_static_mode():
    _state.static_mode = True


def _disable_static_mode():
    _state.static_mode = False


def _in_static_mode():
    return _state.static_mode


def current_program() -> Optional[Program]:
    return _state.main


def default_main_program() -> Program:
    return _state.main if _state.main is not None else _default_main


def default_startup_program() -> Program:
    return _state.startup if _state.startup is not None else _default_startup


@contextlib.contextmanager
def program_guard(main_program, startup_program=None):
    prev_m, prev_s = _state.main, _state.startup
    _state.main = main_program
    _state.startup = startup_program or _default_startup
    # install the recorder hook into the eager op layer
    prev_rec = tensor_mod._op_recorder
    tensor_mod._op_recorder = main_program.record_op
    try:
        yield
    finally:
        _state.main, _state.startup = prev_m, prev_s
        tensor_mod._op_recorder = prev_rec


@contextlib.contextmanager
def name_scope(prefix=None):
    yield


def data(name, shape, dtype="float32", lod_level=0):
    """static.data — feed placeholder. None/-1 dims are materialized as 1 for
    the recording pass; replay is shape-polymorphic in those dims."""
    import jax.numpy as jnp

    from ..core import dtype as dtype_mod

    declared = list(shape)
    shape = [1 if (s is None or (isinstance(s, int) and s < 0)) else int(s) for s in shape]
    d = dtype_mod.convert_dtype(dtype) or dtype_mod.get_default_dtype()
    t = Tensor(jnp.zeros(tuple(shape), d), stop_gradient=True, name=name)
    prog = default_main_program()
    prog.add_feed_var(name, t)
    # keep None/-1 dims distinguishable from literal 1 (ragged pad targets)
    prog.declared_shapes[name] = declared
    return t


class InputSpec:
    def __init__(self, shape, dtype="float32", name=None):
        self.shape = list(shape)
        self.dtype = dtype
        self.name = name

    @classmethod
    def from_tensor(cls, t, name=None):
        return cls(t.shape, str(t.dtype), name or t.name)
