"""SameDiff — the declarative autodiff graph engine (ref:
org.nd4j.autodiff.samediff.SameDiff + SDVariable + internal sessions,
SURVEY.md §1 L3 / §3.2).

Architectural shift vs the reference: dl4j's SameDiff is a **JVM-side op-by-op
interpreter** over an explicit DAG (InferenceSession/TrainingSession dispatch
one JNI call per op per step). Here the same declarative graph API *traces to
a single jaxpr*: ``output()`` and ``fit()`` build a python function that
interprets the DAG symbolically exactly once under ``jax.jit``, so XLA
compiles the WHOLE graph (forward + backward + updater for fit) into one
executable — realizing the native whole-graph execution path the reference
left dormant (libnd4j GraphExecutioner).

Gradients: the reference walks the DAG in reverse topological order calling
each op's hand-written ``doDiff``. Here ``jax.grad`` differentiates the traced
interpretation — no per-op gradient code exists anywhere in this framework.

Op surface: the graph namespaces (sd.math, sd.nn, sd.cnn, sd.rnn, sd.loss,
sd.image, sd.random, sd.bitwise, sd.linalg — ref: generated SDMath/SDNN/...)
read the SAME op-spec registry as the eager namespaces.
"""
from __future__ import annotations

import json
import os
import zipfile
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from deeplearning4j_tpu.ndarray.array import NDArray, _unwrap
from deeplearning4j_tpu.ops import registry as _registry
from deeplearning4j_tpu.train import updaters as _upd
from deeplearning4j_tpu.train import regularization as _rega


class VariableType:
    VARIABLE = "VARIABLE"      # trainable
    CONSTANT = "CONSTANT"
    PLACEHOLDER = "PLACEHOLDER"
    ARRAY = "ARRAY"            # op output


@dataclass
class SDVariable:
    """Symbolic variable (ref: org.nd4j.autodiff.samediff.SDVariable)."""
    sd: "SameDiff"
    name: str
    varType: str
    shape: Optional[Tuple] = None
    dtype: Any = None

    # -- fluent math (a subset of SDVariable's surface; all route via registry)
    def _bin(self, other, opname):
        return self.sd._op("math", opname, [self, other])

    def add(self, other):
        return self._bin(other, "add")

    def sub(self, other):
        return self._bin(other, "sub")

    def mul(self, other):
        return self._bin(other, "mul")

    def div(self, other):
        return self._bin(other, "div")

    def rsub(self, other):
        return self.sd._op("math", "sub", [other, self])

    def rdiv(self, other):
        return self.sd._op("math", "div", [other, self])

    def pow(self, other):
        return self._bin(other, "pow")

    def neg(self):
        return self.sd._op("math", "neg", [self])

    __add__ = add
    __radd__ = add
    __sub__ = sub
    __rsub__ = rsub
    __mul__ = mul
    __rmul__ = mul
    __truediv__ = div
    __rtruediv__ = rdiv
    __pow__ = pow
    __neg__ = neg

    def mmul(self, other):
        return self.sd._op("linalg", "matmul", [self, other])

    __matmul__ = mmul

    def sum(self, *dims, keepdims=False):
        return self.sd._op("reduce", "sum", [self], dims=list(dims) or None, keepdims=keepdims)

    def mean(self, *dims, keepdims=False):
        return self.sd._op("reduce", "mean", [self], dims=list(dims) or None, keepdims=keepdims)

    def max(self, *dims, keepdims=False):
        return self.sd._op("reduce", "max", [self], dims=list(dims) or None, keepdims=keepdims)

    def min(self, *dims, keepdims=False):
        return self.sd._op("reduce", "min", [self], dims=list(dims) or None, keepdims=keepdims)

    def std(self, *dims, biasCorrected=True):
        return self.sd._op("reduce", "std", [self], dims=list(dims) or None,
                           biasCorrected=biasCorrected)

    def argmax(self, dim=None):
        return self.sd._op("reduce", "argmax", [self], dims=dim)

    def reshape(self, *shape):
        if len(shape) == 1 and isinstance(shape[0], (tuple, list)):
            shape = tuple(shape[0])
        return self.sd._op("shape", "reshape", [self], shape=list(shape))

    def transpose(self, *axes):
        return self.sd._op("shape", "transpose", [self], axes=list(axes) or None)

    def rename(self, new_name: str) -> "SDVariable":
        self.sd._rename(self.name, new_name)
        return self

    # -- evaluation
    def eval(self, placeholders: Optional[dict] = None) -> NDArray:
        return self.sd.output(placeholders or {}, [self.name])[self.name]

    def getArr(self) -> Optional[NDArray]:
        v = self.sd._values.get(self.name)
        return NDArray(v) if v is not None else None

    def setArray(self, arr):
        self.sd._values[self.name] = jnp.asarray(_unwrap(arr))

    def gradient(self) -> Optional["SDVariable"]:
        gname = f"grad::{self.name}"
        return self.sd._vars.get(gname)


@dataclass
class SameDiffOp:
    """One graph node (ref: org.nd4j.autodiff.samediff.internal.SameDiffOp)."""
    namespace: str
    opname: str
    inputs: List[str]           # var names (positional)
    outputs: List[str]
    kwargs: dict = field(default_factory=dict)


def _compute_dtype(cfg) -> Optional[Any]:
    """TrainingConfig.computeDtype -> jnp dtype (or None = as-imported)."""
    return {"HALF": jnp.bfloat16, "BFLOAT16": jnp.bfloat16,
            "FLOAT": None, None: None}[
                (cfg.computeDtype or "").upper() or None]


def _cast_fp32_leaves(tree: Dict[str, Any], cdt) -> Dict[str, Any]:
    """Cast float32 leaves to the compute dtype (no-op for cdt None and for
    leaves already cast — the idempotence the frozen pre-cast relies on)."""
    if cdt is None:
        return tree
    return {k: (v.astype(cdt)
                if hasattr(v, "dtype") and v.dtype == jnp.float32 else v)
            for k, v in tree.items()}


@dataclass
class TrainingConfig:
    """(ref: org.nd4j.autodiff.samediff.TrainingConfig).

    ``computeDtype``: mixed-precision training for imported graphs — float32
    leaves (params, constants, float placeholders) are cast to this dtype at
    the top of the traced step, the loss is reduced in float32, and gradients
    land back on the float32 master params through the cast's VJP. "HALF" =
    bfloat16, the TPU-native choice (BASELINE config #4: fp32-as-imported
    leaves the MXU at half rate AND doubles the HBM traffic). None = run in
    the imported dtype."""
    updater: _upd.Updater = field(default_factory=lambda: _upd.Adam(1e-3))
    dataSetFeatureMapping: List[str] = field(default_factory=list)
    dataSetLabelMapping: List[str] = field(default_factory=list)
    regularization: List[_rega.Regularization] = field(default_factory=list)
    minimize: bool = True
    # "BFLOAT16" is the canonical value. "HALF" is accepted as a dl4j-config
    # compatibility alias but ALSO maps to bfloat16 (the reference's
    # DataType.HALF means IEEE float16, which the MXU does not natively
    # train in) — a warning flags the numerics difference at the boundary.
    computeDtype: Optional[str] = None  # None | "BFLOAT16"/"HALF" | "FLOAT"

    def __post_init__(self):
        if (self.computeDtype or "").upper() == "HALF":
            import warnings
            warnings.warn(
                "TrainingConfig.computeDtype='HALF' maps to bfloat16 on "
                "TPU (the reference's HALF is IEEE float16; bf16 shares "
                "fp32's exponent range, so checkpoints/losses will differ "
                "from a CUDA fp16 run in the tails). Use 'BFLOAT16' to "
                "state the TPU dtype explicitly.", stacklevel=3)


class GraphNamespace:
    """Graph op surface generated from the registry (ref: generated SDMath etc.)."""

    def __init__(self, sd: "SameDiff", namespace: str):
        self._sd = sd
        self._namespace = namespace

    def __getattr__(self, opname: str):
        if f"{self._namespace}.{opname}" not in _registry.REGISTRY:
            raise AttributeError(f"no op {self._namespace}.{opname}")

        def call(*args, **kwargs):
            name = None
            if args and isinstance(args[0], str) and self._namespace != "shape":
                name, args = args[0], args[1:]
            sym = [a for a in args]
            return self._sd._op(self._namespace, opname, sym, name=name, **kwargs)

        return call


class SameDiff:
    """The graph container (ref: org.nd4j.autodiff.samediff.SameDiff)."""

    def __init__(self):
        self._vars: Dict[str, SDVariable] = {}
        self._ops: List[SameDiffOp] = []
        self._values: Dict[str, jax.Array] = {}  # VARIABLE/CONSTANT current values
        self._counter = 0
        self._loss_vars: List[str] = []
        self._training_config: Optional[TrainingConfig] = None
        self._opt_state = None
        self._tx = None
        self._jit_cache: Dict = {}
        self._rng_key = jax.random.key(0)
        self.listeners: List[Any] = []
        # graph namespaces
        self.math = GraphNamespace(self, "math")
        self.nn = GraphNamespace(self, "nn")
        self.cnn = GraphNamespace(self, "cnn")
        self.rnn = GraphNamespace(self, "rnn")
        self.loss = GraphNamespace(self, "loss")
        self.image = GraphNamespace(self, "image")
        self.bitwise = GraphNamespace(self, "bitwise")
        self.linalg = GraphNamespace(self, "linalg")
        self.reduce = GraphNamespace(self, "reduce")
        self.shapes = GraphNamespace(self, "shape")
        self.random = GraphNamespace(self, "random")    # ref: SDRandom
        self.updaters = GraphNamespace(self, "updaters")  # ref: updater ops

    @staticmethod
    def create() -> "SameDiff":
        return SameDiff()

    # ------------------------------------------------------------- variables
    def _fresh(self, base: str) -> str:
        while True:
            self._counter += 1
            name = f"{base}_{self._counter}"
            if name not in self._vars:
                return name

    def var(self, name: str, shape_or_value=None, dtype=jnp.float32,
            weightInit: Optional[str] = None, seed: int = 0) -> SDVariable:
        """Trainable variable (ref: SameDiff.var). Accepts an initial value or
        a shape (+ optional WeightInit scheme)."""
        if isinstance(shape_or_value, (tuple, list)) and all(
                isinstance(s, int) for s in shape_or_value):
            shape = tuple(shape_or_value)
            if weightInit:
                from deeplearning4j_tpu.nn.conf import weights as _w
                fan_in = shape[0] if len(shape) > 1 else 1
                fan_out = shape[-1]
                value = _w.init(weightInit, jax.random.fold_in(jax.random.key(seed),
                                                               len(self._vars)),
                                shape, fan_in, fan_out, dtype)
            else:
                value = jnp.zeros(shape, dtype)
        else:
            value = jnp.asarray(_unwrap(shape_or_value), dtype=dtype)
        v = SDVariable(self, name, VariableType.VARIABLE, tuple(value.shape), value.dtype)
        self._vars[name] = v
        self._values[name] = value
        return v

    def constant(self, name_or_value, value=None) -> SDVariable:
        if value is None:
            name, value = self._fresh("const"), name_or_value
        else:
            name = name_or_value
        arr = jnp.asarray(_unwrap(value))
        v = SDVariable(self, name, VariableType.CONSTANT, tuple(arr.shape), arr.dtype)
        self._vars[name] = v
        self._values[name] = arr
        return v

    def placeHolder(self, name: str, shape=None, dtype=jnp.float32) -> SDVariable:
        v = SDVariable(self, name, VariableType.PLACEHOLDER,
                       tuple(shape) if shape else None, jnp.dtype(dtype))
        self._vars[name] = v
        return v

    def _rename(self, old: str, new: str):
        v = self._vars.pop(old)
        v.name = new
        self._vars[new] = v
        if old in self._values:
            self._values[new] = self._values.pop(old)
        for op in self._ops:
            op.inputs = [new if i == old else i for i in op.inputs]
            op.outputs = [new if o == old else o for o in op.outputs]
        self._loss_vars = [new if l == old else l for l in self._loss_vars]
        self._jit_cache.clear()

    def variables(self) -> List[SDVariable]:
        return list(self._vars.values())

    def getVariable(self, name: str) -> SDVariable:
        return self._vars[name]

    def hasVariable(self, name: str) -> bool:
        return name in self._vars

    # ------------------------------------------------------------------ ops
    def _op(self, namespace: str, opname: str, sym_inputs: Sequence, name=None,
            n_outputs: Optional[int] = None, **kwargs) -> Union[SDVariable, Tuple]:
        """Append a node. Inputs may be SDVariables or literals (literals become
        constants). Output arity is discovered by abstract evaluation."""
        spec = _registry.get(opname, namespace)
        in_names = []
        for a in sym_inputs:
            if isinstance(a, SDVariable):
                in_names.append(a.name)
            elif isinstance(a, (int, float, bool)):
                c = self.constant(self._fresh("lit"), a)
                in_names.append(c.name)
            else:
                c = self.constant(self._fresh("const"), a)
                in_names.append(c.name)

        # abstract-eval to learn output structure/shapes (placeholder None dims -> 2)
        def abstract(n):
            v = self._vars[n]
            if n in self._values:
                return jax.ShapeDtypeStruct(self._values[n].shape, self._values[n].dtype)
            shape = tuple(2 if s is None else s for s in (v.shape or ()))
            return jax.ShapeDtypeStruct(shape, v.dtype or jnp.float32)

        try:
            out_struct = jax.eval_shape(lambda *xs: spec.fn(*xs, **kwargs),
                                        *[abstract(n) for n in in_names])
        except Exception:
            out_struct = None

        multi = isinstance(out_struct, (tuple, list))
        count = len(out_struct) if multi else 1
        base = name or self._fresh(opname)
        out_names = [base] if not multi else [f"{base}#{i}" for i in range(count)]
        self._ops.append(SameDiffOp(namespace, opname, in_names, out_names, dict(kwargs)))
        outs = []
        flat_struct = out_struct if multi else [out_struct]
        for i, on in enumerate(out_names):
            st = flat_struct[i] if flat_struct and flat_struct[i] is not None else None

            def mkvar(on, st):
                shape = tuple(st.shape) if st is not None and hasattr(st, "shape") else None
                dt = st.dtype if st is not None and hasattr(st, "dtype") else None
                return SDVariable(self, on, VariableType.ARRAY, shape, dt)

            if st is not None and isinstance(st, (tuple, list)):
                # nested (e.g. lstmLayer second output (h,c)) — flatten naming
                sub = []
                for j, s in enumerate(st):
                    nm = f"{on}.{j}"
                    v = mkvar(nm, s)
                    self._vars[nm] = v
                    sub.append(v)
                # register a passthrough structural var
                self._vars[on] = SDVariable(self, on, VariableType.ARRAY, None, None)
                outs.append(tuple(sub))
            else:
                v = mkvar(on, st)
                self._vars[on] = v
                outs.append(v)
        self._jit_cache.clear()
        return tuple(outs) if multi else outs[0]

    def convertToVariable(self, var) -> SDVariable:
        """Constant -> trainable VARIABLE in place (ref:
        SameDiff.convertToVariable; used to fine-tune imported frozen graphs
        whose weights arrive as constants)."""
        v = var if isinstance(var, SDVariable) else self._vars[var]
        if v.varType == VariableType.CONSTANT:
            v.varType = VariableType.VARIABLE
            self._jit_cache.clear()
        return v

    def convertAllConstantsToVariables(self, min_size: int = 3) -> int:
        """Make every float constant with ≥ min_size elements trainable —
        the standard prelude to fine-tuning an imported frozen graph (small
        constants are attribute carriers: axes, scales, epsilons). Returns
        the number converted."""
        n = 0
        for v in list(self._vars.values()):
            if v.varType == VariableType.CONSTANT and v.shape \
                    and v.dtype is not None and "float" in str(v.dtype) \
                    and int(np.prod(v.shape)) >= min_size:
                self.convertToVariable(v)
                n += 1
        return n

    def fuseAttention(self) -> int:
        """Collapse imported matmul->[scale]->softmax->matmul attention
        chains onto the kernel-backed ``scaledDotProductAttentionFused``
        op (beyond-parity — see autodiff/rewrites.py for the matched
        pattern and its guarantees). Returns the number of sites fused.
        Typical use, mirroring the reference's fine-tune prelude::

            sd = TensorflowFrameworkImporter.runImport(graph_def)
            sd.convertAllConstantsToVariables()
            sd.fuseAttention()        # optional kernel-fusion pass
        """
        from deeplearning4j_tpu.autodiff.rewrites import fuse_attention
        return fuse_attention(self)

    def convertToConstant(self, var) -> SDVariable:
        """VARIABLE -> frozen constant in place (ref: SameDiff.convertToConstant)."""
        v = var if isinstance(var, SDVariable) else self._vars[var]
        if v.varType == VariableType.VARIABLE:
            v.varType = VariableType.CONSTANT
            self._jit_cache.clear()
        return v

    # ----------------------------------------------------------- control flow
    # The reference interprets Enter/Exit/Merge/Switch/NextIteration nodes in
    # InferenceSession (SURVEY §3.2 — o.n.linalg.api.ops.impl.controlflow).
    # TPU-native equivalent: STRUCTURED control flow — each construct is one
    # graph node holding traced sub-graphs, lowered to lax.cond /
    # lax.while_loop / lax.scan inside the single jitted executable (XLA
    # requires structured control flow; dataflow-style Switch/Merge cannot be
    # expressed under jit).

    def _trace_subgraph(self, fn, arg_vars: Sequence[SDVariable], extra_args: int = 0):
        """Run a SameDiffLambda-style ``fn(sub_sd, *args)`` against a fresh
        sub-SameDiff whose placeholders mirror ``arg_vars`` (+ ``extra_args``
        leading scalar int args, e.g. a loop counter)."""
        sub = SameDiff()
        args = []
        for i in range(extra_args):
            args.append(sub.placeHolder(f"__arg{i}", shape=(), dtype=jnp.int32))
        for i, v in enumerate(arg_vars):
            # unknown dims -> 2, the same convention _op's abstract eval uses
            shape = tuple(2 if s is None else s for s in (v.shape or ()))
            args.append(sub.placeHolder(f"__sgin{len(args)}", shape=shape,
                                        dtype=v.dtype or jnp.float32))
        out = fn(sub, *args)
        outs = list(out) if isinstance(out, (tuple, list)) else [out]
        return sub, [a.name for a in args], [o.name for o in outs]

    def _run_subgraph(self, sub: "SameDiff", in_names, in_vals, out_names):
        env = {**sub._values, **dict(zip(in_names, in_vals))}
        env = sub._interpret(env)
        return [env[n] for n in out_names]

    def _control_op(self, opname: str, input_vars: Sequence[SDVariable],
                    kwargs: dict, name: Optional[str]):
        """Append a control-flow node; output shapes via abstract eval."""
        in_names = [v.name for v in input_vars]
        base = name or self._fresh(opname)

        def absval(v):
            # unknown dims -> 2, matching _op's abstract-eval convention
            shape = tuple(2 if s is None else s for s in (v.shape or ()))
            return jax.ShapeDtypeStruct(shape, v.dtype or jnp.float32)

        node = SameDiffOp("control", opname, in_names, [], kwargs)
        try:
            out_struct = jax.eval_shape(
                lambda *xs: tuple(self._exec_control(node, list(xs))),
                *[absval(v) for v in input_vars])
        except Exception:
            out_struct = None
        # fallback arity: while/for return one value per input; "if" returns
        # one per input minus the predicate
        count = len(out_struct) if out_struct is not None else (
            len(in_names) - 1 if opname == "if" else len(in_names))
        node.outputs = [base] if count == 1 else [f"{base}#{i}" for i in range(count)]
        self._ops.append(node)
        outs = []
        for i, on in enumerate(node.outputs):
            st = out_struct[i] if out_struct is not None else None
            v = SDVariable(self, on, VariableType.ARRAY,
                           tuple(st.shape) if st is not None else None,
                           st.dtype if st is not None else None)
            self._vars[on] = v
            outs.append(v)
        self._jit_cache.clear()
        return outs[0] if len(outs) == 1 else tuple(outs)

    def _exec_control(self, node: SameDiffOp, args: list):
        """Lower one control node onto lax primitives (called while tracing)."""
        kw = node.kwargs
        if node.opname == "if":
            (sub_t, tin, tout) = kw["true_graph"]
            (sub_f, fin, fout) = kw["false_graph"]
            pred, rest = args[0], args[1:]
            return jax.lax.cond(
                jnp.asarray(pred).astype(bool).reshape(()),
                lambda xs: tuple(self._run_subgraph(sub_t, tin, xs, tout)),
                lambda xs: tuple(self._run_subgraph(sub_f, fin, xs, fout)),
                tuple(rest))
        if node.opname == "while":
            (sub_c, cin, cout) = kw["cond_graph"]
            (sub_b, bin_, bout) = kw["body_graph"]
            state = tuple(jnp.asarray(a) for a in args)
            # loop vars keep their initial dtypes (TF while-loop semantics;
            # also guards against literal-promotion drift after serde)
            dts = [s.dtype for s in state]

            def body(s):
                new = self._run_subgraph(sub_b, bin_, list(s), bout)
                return tuple(jnp.asarray(n).astype(d) for n, d in zip(new, dts))

            return tuple(jax.lax.while_loop(
                lambda s: jnp.asarray(self._run_subgraph(sub_c, cin, list(s), cout)[0])
                .astype(bool).reshape(()),
                body, state))
        if node.opname == "for":
            (sub_b, bin_, bout) = kw["body_graph"]
            n_iter = kw["n_iter"]
            state0 = tuple(jnp.asarray(a) for a in args)
            dts = [s.dtype for s in state0]

            def body(state, i):
                new = self._run_subgraph(sub_b, bin_, [i, *state], bout)
                return tuple(jnp.asarray(n).astype(d)
                             for n, d in zip(new, dts)), None

            out, _ = jax.lax.scan(body, state0, jnp.arange(n_iter))
            return out
        raise ValueError(f"unknown control op {node.opname}")

    def ifCond(self, cond, trueBody, falseBody, inputs=(), name: Optional[str] = None):
        """Conditional (ref: SameDiff.ifCond — Switch/Merge in the reference;
        lax.cond here, differentiable). ``cond`` is a scalar-bool SDVariable in
        THIS graph; trueBody/falseBody are ``fn(sub_sd, *inputs)`` lambdas
        (ref: SameDiffLambda.define) returning one or more sub-graph vars."""
        inputs = list(inputs)
        tg = self._trace_subgraph(trueBody, inputs)
        fg = self._trace_subgraph(falseBody, inputs)
        assert len(tg[2]) == len(fg[2]), "branches must return the same arity"
        return self._control_op("if", [cond, *inputs],
                                {"true_graph": tg, "false_graph": fg}, name)

    def whileLoop(self, loopVars, condBody, loopBody, name: Optional[str] = None):
        """While loop (ref: SameDiff.whileLoop — Enter/Exit/NextIteration in
        the reference; lax.while_loop here). ``condBody(sub_sd, *state)`` must
        return a scalar bool; ``loopBody(sub_sd, *state)`` returns the next
        state (same arity/shapes). NOTE: like XLA, reverse-mode gradients do
        not flow through a general while loop — use forLoop for trainable
        iteration."""
        loopVars = list(loopVars)
        cg = self._trace_subgraph(condBody, loopVars)
        bg = self._trace_subgraph(loopBody, loopVars)
        assert len(bg[2]) == len(loopVars), "body must return one var per loop var"
        return self._control_op("while", loopVars,
                                {"cond_graph": cg, "body_graph": bg}, name)

    def forLoop(self, n_iter: int, loopVars, loopBody, name: Optional[str] = None):
        """Fixed-trip-count loop lowered to lax.scan — differentiable, the
        TPU-idiomatic replacement for trainable while loops.
        ``loopBody(sub_sd, i, *state)`` returns the next state."""
        loopVars = list(loopVars)
        bg = self._trace_subgraph(loopBody, loopVars, extra_args=1)
        assert len(bg[2]) == len(loopVars), "body must return one var per loop var"
        return self._control_op("for", loopVars,
                                {"body_graph": bg, "n_iter": int(n_iter)}, name)

    # ------------------------------------------------------------- execution
    def _needed_ops(self, output_names) -> List[SameDiffOp]:
        """Ancestor-subgraph pruning (ref: AbstractSession executes only ops
        required for the requested variables)."""
        needed = set()
        for n in output_names:
            needed.add(n.split(".")[0] if "." in n else n)
        keep = []
        for node in reversed(self._ops):
            if any(o in needed for o in node.outputs):
                keep.append(node)
                needed.update(node.inputs)
        return list(reversed(keep))

    def _interpret(self, values: Dict[str, Any], only_ops: Optional[List[SameDiffOp]] = None
                   ) -> Dict[str, Any]:
        """Topologically interpret the DAG over concrete/traced values. Runs
        under jit — each registry fn call traces into the single jaxpr."""
        env = dict(values)
        for node in (only_ops if only_ops is not None else self._ops):
            args = [env[i] for i in node.inputs]
            if node.namespace == "control":
                out = self._exec_control(node, args)
                if len(node.outputs) == 1:
                    out = out[0]
            else:
                spec = _registry.get(node.opname, node.namespace)
                out = spec.fn(*args, **node.kwargs)
            if len(node.outputs) == 1 and not isinstance(out, (tuple, list)):
                env[node.outputs[0]] = out
            else:
                for on, o in zip(node.outputs, out):
                    if isinstance(o, (tuple, list)):
                        for j, oo in enumerate(o):
                            env[f"{on}.{j}"] = oo
                        env[on] = o
                    else:
                        env[on] = o
        return env

    def _exec_fn(self, output_names: Tuple[str, ...]):
        """Build + cache the jitted whole-graph executor for given outputs."""
        key = ("exec", output_names)
        if key not in self._jit_cache:
            ops = self._needed_ops(output_names)

            def fn(var_values, placeholder_values):
                env = {**var_values, **placeholder_values}
                env = self._interpret(env, only_ops=ops)
                return {n: env[n] for n in output_names}

            self._jit_cache[key] = jax.jit(fn)
        return self._jit_cache[key]

    def output(self, placeholders: Dict[str, Any], outputs: Union[str, Sequence[str]]
               ) -> Dict[str, NDArray]:
        """Whole-graph compiled inference (ref: SameDiff.output/batchOutput)."""
        if isinstance(outputs, str):
            outputs = [outputs]
        removed = getattr(self, "_removed_by_rewrite", None)
        if removed:
            for n in outputs:
                base = n.split(".")[0] if "." in n else n
                if base in removed:
                    raise ValueError(
                        f"variable '{n}' was an attention-chain intermediate "
                        f"removed by the {removed[base]} graph rewrite and "
                        f"can no longer be computed; request it before "
                        f"fusing, or skip the rewrite to keep it")
        ph = {k: jnp.asarray(_unwrap(v)) for k, v in placeholders.items()}
        fn = self._exec_fn(tuple(outputs))
        out = fn(self._values, ph)
        return {k: NDArray(v) for k, v in out.items()}

    def batchOutput(self):
        return _BatchOutputBuilder(self)

    def evaluate(self, iterator, outputVariable: str, evaluation=None):
        """Evaluate a dataset against one output variable (ref:
        SameDiff.evaluate(DataSetIterator, String, IEvaluation...)).
        Placeholder names come from the TrainingConfig's feature/label
        mappings; labels feed the evaluation, not the graph."""
        from deeplearning4j_tpu.eval import Evaluation
        cfg = self._training_config
        assert cfg is not None and cfg.dataSetFeatureMapping, \
            "setTrainingConfig with dataSetFeatureMapping first"
        ev = evaluation if evaluation is not None else Evaluation()
        if hasattr(iterator, "reset"):
            iterator.reset()
        for ds in iterator:
            feats = ds.features if isinstance(ds.features, (list, tuple)) \
                else [ds.features]
            ph = {n: f for n, f in zip(cfg.dataSetFeatureMapping, feats)}
            out = self.output(ph, outputVariable)[outputVariable]
            ev.eval(ds.labels, out.toNumpy(),
                    mask=getattr(ds, "labels_mask", None))
        return ev

    # ------------------------------------------------------------- training
    def setLossVariables(self, *names):
        self._loss_vars = [n.name if isinstance(n, SDVariable) else n for n in names]
        self._jit_cache.clear()

    def getLossVariables(self):
        return list(self._loss_vars)

    def setTrainingConfig(self, cfg: TrainingConfig):
        self._training_config = cfg
        self._tx = cfg.updater.to_optax()
        self._opt_state = None
        self._jit_cache.clear()

    def _trainable_names(self) -> List[str]:
        return [n for n, v in self._vars.items() if v.varType == VariableType.VARIABLE]

    def _train_step_fn(self):
        key = "train_step"
        if key not in self._jit_cache:
            self._jit_cache[key] = jax.jit(self._train_step_inner(),
                                           donate_argnums=(0, 2))
        return self._jit_cache[key]

    # steps fused into one executable by fit()'s multi-step path — same
    # de-dispatch rationale as MultiLayerNetwork.fuseSteps (per-dispatch
    # host latency is a large share of a small whole-graph step)
    fuseSteps: int = 8
    # how many fused chunks score-only listener callbacks may lag the
    # dispatch head before a forced batched replay (staleness bound; the
    # replay itself is one bulk device->host transfer — see _ReplayQueue).
    # 0 = replay right after each chunk (live streaming, pays one host
    # round trip per chunk)
    listenerReplayLag: int = 16

    def _train_multi_fn(self):
        key = "train_multi"
        if key not in self._jit_cache:
            step_inner = self._train_step_inner()

            def multi(trainables, opt_state, frozen, ph_stacked):
                def body(carry, ph):
                    tr, opt = carry
                    tr, opt, loss = step_inner(tr, frozen, opt, ph)
                    return (tr, opt), loss

                (trainables, opt_state), losses = jax.lax.scan(
                    body, (trainables, opt_state), ph_stacked)
                return trainables, opt_state, losses

            self._jit_cache[key] = jax.jit(multi, donate_argnums=(0, 1))
        return self._jit_cache[key]

    def _train_step_inner(self):
        """The un-jitted single training step (fwd+bwd+update) shared by the
        per-step executable and the fused lax.scan."""
        key = "train_step_inner"
        if key not in self._jit_cache:
            t_names = tuple(self._trainable_names())
            loss_names = tuple(self._loss_vars)
            cfg = self._training_config
            ops = self._needed_ops(loss_names)
            cdt = _compute_dtype(cfg)

            def cast_tree(tree):
                return _cast_fp32_leaves(tree, cdt)

            def loss_fn(trainables, frozen, placeholders):
                env = {**cast_tree(frozen), **cast_tree(trainables),
                       **cast_tree(placeholders)}
                env = self._interpret(env, only_ops=ops)
                loss = sum(jnp.sum(env[l].astype(jnp.float32))
                           for l in loss_names)
                for reg in cfg.regularization:
                    for n in t_names:
                        loss = loss + reg.penalty(trainables[n])
                return loss if cfg.minimize else -loss

            def step(trainables, frozen, opt_state, placeholders):
                loss, grads = jax.value_and_grad(loss_fn)(
                    trainables, frozen, placeholders)
                updates, opt_state = self._tx.update(grads, opt_state,
                                                     trainables)
                trainables = jax.tree_util.tree_map(
                    lambda p, u: p + u, trainables, updates)
                return trainables, opt_state, loss

            self._jit_cache[key] = step
        return self._jit_cache[key]

    def fit(self, data, epochs: int = 1):
        """Train (ref: SameDiff.fit(MultiDataSetIterator)): one jitted step =
        full fwd + bwd + updater. ``data`` is a DataSetIterator/DataSet or a
        dict of placeholder arrays per batch."""
        from deeplearning4j_tpu.data.dataset import DataSet, DataSetIterator, ListDataSetIterator
        cfg = self._training_config
        assert cfg is not None, "call setTrainingConfig first"
        assert self._loss_vars, "call setLossVariables first"
        if isinstance(data, DataSet):
            data = ListDataSetIterator([data])
        elif isinstance(data, dict):
            data = [data]  # one batch of explicit placeholder arrays

        t_names = self._trainable_names()
        trainables = {n: self._values[n] for n in t_names}
        frozen = {n: v for n, v in self._values.items() if n not in trainables}
        # Cast frozen fp32 leaves ONCE per fit call (constants, imported
        # frozen weights): the in-step cast then no-ops on them —
        # frozen-weight HBM reads happen at bf16 width every step instead
        # of fp32-read-plus-cast. Trainables keep fp32 masters (cast
        # inside the step so gradients land on the masters).
        frozen = _cast_fp32_leaves(frozen, _compute_dtype(cfg))
        if self._opt_state is None:
            self._opt_state = self._tx.init(trainables)
        step = self._train_step_fn()
        history = []
        # De-dispatch: steps buffer into fuseSteps-sized lax.scan chunks —
        # one dispatch each (see fuseSteps). Listeners no longer
        # disable fusing (round-5, mirroring MultiLayerNetwork): chunks are
        # cut at iterations where a listener needs the LIVE model
        # (requiresModelAtIteration), and buffered per-step losses are
        # replayed to listeners after each chunk — identical callback
        # sequence to the per-step path.
        fuse_k = max(self.fuseSteps, 0)
        buf: list = []  # host placeholder dicts of identical shapes

        def ph_host(ds):
            if isinstance(ds, dict):
                return {k: _unwrap(v) for k, v in ds.items()}
            ph = {}
            feats = ds.features if isinstance(ds.features, (list, tuple)) else [ds.features]
            labs = ds.labels if isinstance(ds.labels, (list, tuple)) else [ds.labels]
            for nm, arr in zip(cfg.dataSetFeatureMapping, feats):
                ph[nm] = _unwrap(arr)
            for nm, arr in zip(cfg.dataSetLabelMapping, labs):
                ph[nm] = _unwrap(arr)
            return ph

        def _sig(ph):
            # dtype is part of the signature: same-shaped batches of
            # different dtypes must not np.stack into one chunk (the
            # promotion would silently train on different numerics than
            # the per-step path — round-4 advisor finding). result_type
            # reads the dtype without forcing a device->host transfer.
            return tuple(sorted((k, np.shape(v), str(jnp.result_type(v)))
                                for k, v in ph.items()))

        # Lagged, batched listener replay — the SHARED queue (see
        # nn.multilayer._ReplayQueue): with listeners, drained chunks'
        # losses move device->host in ONE batched transfer (a host read
        # waits for the device, so per-chunk syncing would serialize the
        # fused pipeline on per-dispatch host latency). Score-only listeners get
        # their callbacks LATE — batched at fit end / every
        # listenerReplayLag chunks — but in exact order with exact scores;
        # listeners that need the live model flush synchronously at their
        # declared boundaries (rq.push).
        from deeplearning4j_tpu.nn.multilayer import _ReplayQueue, _chunk_limit

        def _replay(losses, k):
            for j in range(k):
                history.append(losses[j])
                self._score = losses[j]
                for lst in self.listeners:
                    lst.iterationDone(self, len(history), 0)

        rq = _ReplayQueue(self, replay=_replay)
        rq.dispatched = 0   # iteration numbers are per-fit (len(history))

        def run_single(ph):
            nonlocal trainables
            rq.drain()   # keep callback order: chunks before this step
            phj = {k: jnp.asarray(v) for k, v in ph.items()}
            trainables, self._opt_state, loss = step(trainables, frozen,
                                                     self._opt_state, phj)
            rq.dispatched += 1
            history.append(loss)   # device scalar; bulk-synced below
            self._score = loss
            # listeners read current values (StatsListener param stats)
            self._values.update(trainables)
            for lst in self.listeners:
                lst.iterationDone(self, len(history), 0)

        def flush(buf):
            nonlocal trainables
            while buf:
                k = _chunk_limit(self.listeners, rq.dispatched, fuse_k)
                if k <= 1:
                    # a listener needs the live model at the very next
                    # iteration: run it as a single (exact semantics)
                    run_single(buf[0])
                    buf = buf[1:]
                    continue
                if len(buf) < k:
                    break
                chunk, buf = buf[:k], buf[k:]
                stacked = {key: jnp.asarray(np.stack([c[key] for c in chunk]))
                           for key in chunk[0]}
                multi = self._train_multi_fn()
                trainables, self._opt_state, losses = multi(
                    trainables, self._opt_state, frozen, stacked)
                # rebind after every chunk: the jit donated the previous
                # buffers, and self._values must never dangle on deleted
                # arrays if a later batch raises mid-fit. rq.push replays
                # synchronously when a boundary listener needs the model
                # as of this chunk end, lagged+batched otherwise.
                self._values.update(trainables)
                rq.push(losses, k)
            return buf

        try:
            for _ in range(epochs):
                for ds in data:
                    ph = ph_host(ds)
                    if fuse_k > 1:
                        if buf and _sig(buf[0]) != _sig(ph):
                            for b in buf:   # shape change: drain as singles
                                run_single(b)
                            buf = []
                        buf.append(ph)
                        buf = flush(buf)
                    else:
                        run_single(ph)
            for b in buf:   # leftover (< fuseSteps) steps run individually
                run_single(b)
            rq.drain()
        except BaseException:
            # an exception mid-fit must not lose the callbacks/scores of
            # chunks that DID complete (pending holds completed chunks
            # only); never mask the original error with a replay failure
            try:
                rq.drain()
            except Exception:
                pass
            raise
        self._values.update(trainables)
        if history:
            # ONE bulk device->host transfer for whatever is still on
            # device. Replayed entries are already host floats (listener
            # path) — re-stacking those onto the device just to read them
            # back would cost a second round trip.
            dev = [(i, h) for i, h in enumerate(history)
                   if not isinstance(h, float)]
            if dev:
                vals = np.asarray(jnp.stack([h for _, h in dev])).astype(float)
                for (i, _), v in zip(dev, vals):
                    history[i] = float(v)
            history = [float(h) for h in history]
        return history

    def score(self) -> float:
        """Last training loss (ref: the reference's SameDiff training score
        surfaces through History/listeners; models expose score() here)."""
        return float(getattr(self, "_score", float("nan")))

    def numParams(self) -> int:
        import numpy as _np
        return int(sum(_np.size(self._values[n])
                       for n in self._trainable_names()))

    def calculateGradients(self, placeholders: Dict[str, Any], wrt: Sequence[str]
                           ) -> Dict[str, NDArray]:
        """Explicit gradient computation (ref: SameDiff.calculateGradients).
        Also materializes grad::<name> variables (ref: SDVariable.gradient())."""
        assert self._loss_vars, "setLossVariables first"
        loss_names = tuple(self._loss_vars)
        wrt = [w.name if isinstance(w, SDVariable) else w for w in wrt]

        ops = self._needed_ops(loss_names)

        def loss_fn(sel, rest, ph):
            env = {**rest, **sel, **ph}
            env = self._interpret(env, only_ops=ops)
            return sum(jnp.sum(env[l]) for l in loss_names)

        sel = {n: self._values[n] for n in wrt}
        rest = {n: v for n, v in self._values.items() if n not in sel}
        ph = {k: jnp.asarray(_unwrap(v)) for k, v in placeholders.items()}
        grads = jax.jit(jax.grad(loss_fn))(sel, rest, ph)
        out = {}
        for n, g in grads.items():
            gname = f"grad::{n}"
            self._vars[gname] = SDVariable(self, gname, VariableType.ARRAY,
                                           tuple(g.shape), g.dtype)
            self._values[gname] = g
            out[n] = NDArray(g)
        return out

    # ------------------------------------------------------------ persistence
    def save(self, path: str, save_updater_state: bool = False):
        """Zip: graph.json + weights .npy blobs (ref: SameDiff.save — the
        reference uses FlatBuffers; JSON+npz is this framework's container,
        with the same contract: graph + weights + optional updater state)."""
        graph = {
            "vars": [{"name": v.name, "type": v.varType,
                      "shape": list(v.shape) if v.shape else None,
                      "dtype": str(v.dtype) if v.dtype is not None else None}
                     for v in self._vars.values() if "." not in v.name],
            "ops": [_op_to_dict(o) for o in self._ops],
            "loss": self._loss_vars,
        }
        removed = getattr(self, "_removed_by_rewrite", None)
        if removed:
            # keep the targeted removed-by-rewrite error working across a
            # save/load roundtrip (else it degrades back to a deep KeyError)
            graph["removed_by_rewrite"] = removed
        with zipfile.ZipFile(path, "w") as zf:
            zf.writestr("graph.json", json.dumps(graph, indent=2))
            manifest = []
            for n, val in self._values.items():
                if self._vars[n].varType in (VariableType.VARIABLE, VariableType.CONSTANT):
                    import io
                    buf = io.BytesIO()
                    np.save(buf, np.asarray(val))
                    zf.writestr(f"values/{n}.npy", buf.getvalue())
                    manifest.append({"name": n, "type": self._vars[n].varType})
            zf.writestr("values.json", json.dumps(manifest))
            if save_updater_state and self._training_config is not None:
                from deeplearning4j_tpu.train import updaters as _updz
                cfg = self._training_config
                zf.writestr("training.json", json.dumps({
                    "updater": cfg.updater.to_dict(),
                    "dataSetFeatureMapping": cfg.dataSetFeatureMapping,
                    "dataSetLabelMapping": cfg.dataSetLabelMapping,
                    "minimize": cfg.minimize,
                    "computeDtype": cfg.computeDtype,
                    "hasOptState": self._opt_state is not None,
                }))
                if self._opt_state is not None:
                    import io
                    leaves = jax.tree_util.tree_leaves(self._opt_state)
                    for i, leaf in enumerate(leaves):
                        buf = io.BytesIO()
                        np.save(buf, np.asarray(leaf))
                        zf.writestr(f"updaterState/{i}.npy", buf.getvalue())

    @staticmethod
    def load(path: str) -> "SameDiff":
        sd = SameDiff()
        with zipfile.ZipFile(path) as zf:
            graph = json.loads(zf.read("graph.json"))
            manifest = json.loads(zf.read("values.json"))
            values = {}
            for m in manifest:
                import io
                values[m["name"]] = (m["type"], np.load(io.BytesIO(zf.read(f"values/{m['name']}.npy"))))
        for vd in graph["vars"]:
            name = vd["name"]
            if name in values:
                vtype, arr = values[name]
                if vtype == VariableType.VARIABLE:
                    sd.var(name, arr, dtype=arr.dtype)
                else:
                    sd.constant(name, arr)
            elif vd["type"] == VariableType.PLACEHOLDER:
                sd.placeHolder(name, shape=vd["shape"],
                               dtype=vd["dtype"] or jnp.float32)
            else:
                sd._vars[name] = SDVariable(sd, name, vd["type"],
                                            tuple(vd["shape"]) if vd["shape"] else None,
                                            vd["dtype"])
        for od in graph["ops"]:
            sd._ops.append(_op_from_dict(od))
            for on in od["outputs"]:
                if on not in sd._vars:
                    sd._vars[on] = SDVariable(sd, on, VariableType.ARRAY)
        sd._loss_vars = graph.get("loss", [])
        if graph.get("removed_by_rewrite"):
            sd._removed_by_rewrite = dict(graph["removed_by_rewrite"])

        # updater state: rebuild the optax tree structurally (tx.init on the
        # restored trainables) and refill its leaves in flatten order — the
        # exact-resume contract (ref: SameDiff FlatBuffers updaterState)
        with zipfile.ZipFile(path) as zf:
            names = set(zf.namelist())
            if "training.json" in names:
                import io
                from deeplearning4j_tpu.train import updaters as _updz
                tj = json.loads(zf.read("training.json"))
                sd.setTrainingConfig(TrainingConfig(
                    updater=_updz.from_dict(tj["updater"]),
                    dataSetFeatureMapping=tj.get("dataSetFeatureMapping", []),
                    dataSetLabelMapping=tj.get("dataSetLabelMapping", []),
                    minimize=tj.get("minimize", True),
                    computeDtype=tj.get("computeDtype")))
                if tj.get("hasOptState"):
                    trainables = {n: sd._values[n] for n in sd._trainable_names()}
                    skeleton = sd._tx.init(trainables)
                    leaves, treedef = jax.tree_util.tree_flatten(skeleton)
                    loaded = []
                    for i, ref in enumerate(leaves):
                        arr = np.load(io.BytesIO(zf.read(f"updaterState/{i}.npy")))
                        loaded.append(jnp.asarray(arr, dtype=ref.dtype)
                                      if hasattr(ref, "dtype") else arr)
                    sd._opt_state = jax.tree_util.tree_unflatten(treedef, loaded)
        return sd

    def summary(self) -> str:
        lines = [f"SameDiff: {len(self._vars)} variables, {len(self._ops)} ops"]
        for o in self._ops:
            lines.append(f"  {','.join(o.outputs)} = {o.namespace}.{o.opname}({', '.join(o.inputs)})")
        return "\n".join(lines)


def _enc_kw_val(v):
    """JSON-encode one kwarg value. Python slice objects (stridedSlice's
    'slices' tuple — what TF's mask[:, newaxis, newaxis, :] imports to)
    get a tagged form so load() restores REAL slices, not their repr."""
    if isinstance(v, slice):
        return {"__slice__": [v.start, v.stop, v.step]}
    if isinstance(v, (list, tuple)):
        return [_enc_kw_val(x) for x in v]
    if isinstance(v, (int, float, str, bool)) or v is None:
        return v
    return str(v)


def _dec_kw_val(v):
    if isinstance(v, dict) and "__slice__" in v:
        s = v["__slice__"]
        return slice(s[0], s[1], s[2])
    if isinstance(v, list):
        return [_dec_kw_val(x) for x in v]
    return v


def _json_safe(d):
    return {k: _enc_kw_val(v) for k, v in d.items()}


_SUBGRAPH_KEYS = ("true_graph", "false_graph", "cond_graph", "body_graph")


def _op_to_dict(o: SameDiffOp) -> dict:
    """Serialize one node; control nodes recurse into their sub-graphs."""
    kw = dict(o.kwargs)
    if o.namespace == "control":
        # non-subgraph kwargs go through the SAME tagged encoder as every
        # other op so slice-valued kwargs round-trip serde uniformly
        # (previously they fell through as raw repr strings)
        for k, v in kw.items():
            if k in _SUBGRAPH_KEYS:
                sub, ins, outs = v
                kw[k] = {"__subgraph__": _subgraph_to_dict(sub),
                         "in": ins, "out": outs}
            else:
                kw[k] = _enc_kw_val(v)
    else:
        kw = _json_safe(kw)
    return {"namespace": o.namespace, "op": o.opname, "inputs": o.inputs,
            "outputs": o.outputs, "kwargs": kw}


def _op_from_dict(od: dict) -> SameDiffOp:
    kw = dict(od["kwargs"])
    if od["namespace"] == "control":
        for k, v in kw.items():
            if k in _SUBGRAPH_KEYS:
                kw[k] = (_subgraph_from_dict(v["__subgraph__"]), v["in"], v["out"])
            else:
                kw[k] = _dec_kw_val(v)
    else:
        kw = {k: _dec_kw_val(v) for k, v in kw.items()}
    return SameDiffOp(od["namespace"], od["op"], od["inputs"], od["outputs"], kw)


def _subgraph_to_dict(sd: "SameDiff") -> dict:
    """Control sub-graphs carry their constants inline (they are small —
    literals and shape params; top-level weights stay in npy blobs)."""
    return {
        "vars": [{"name": v.name, "type": v.varType,
                  "shape": list(v.shape) if v.shape else None,
                  "dtype": str(v.dtype) if v.dtype is not None else None}
                 for v in sd._vars.values() if "." not in v.name],
        "ops": [_op_to_dict(o) for o in sd._ops],
        "values": {n: {"data": np.asarray(v).tolist(), "dtype": str(v.dtype)}
                   for n, v in sd._values.items()},
    }


def _subgraph_from_dict(d: dict) -> "SameDiff":
    sub = SameDiff()
    for vd in d["vars"]:
        sub._vars[vd["name"]] = SDVariable(
            sub, vd["name"], vd["type"],
            tuple(vd["shape"]) if vd["shape"] else None, vd["dtype"])
    for n, spec in d["values"].items():
        sub._values[n] = jnp.asarray(np.asarray(spec["data"], dtype=spec["dtype"]))
    for od in d["ops"]:
        sub._ops.append(_op_from_dict(od))
        for on in od["outputs"]:
            if on not in sub._vars:
                sub._vars[on] = SDVariable(sub, on, VariableType.ARRAY)
    return sub


class _BatchOutputBuilder:
    """(ref: SameDiff.batchOutput fluent API)."""

    def __init__(self, sd: SameDiff):
        self._sd = sd
        self._ph = {}
        self._outputs = []

    def input(self, name, arr):
        self._ph[name] = arr
        return self

    def output(self, *names):
        self._outputs.extend(n.name if isinstance(n, SDVariable) else n for n in names)
        return self

    def execSingle(self) -> NDArray:
        return self._sd.output(self._ph, self._outputs)[self._outputs[0]]

    def exec(self) -> Dict[str, NDArray]:
        return self._sd.output(self._ph, self._outputs)
