"""Abstract interpretation of jaxprs for quantization-contract linting.

The analyzer traces a program with ``jax.make_jaxpr`` on
``ShapeDtypeStruct`` arguments (nothing executes) and walks the jaxpr
propagating, per intermediate value:

- ``dtype`` / ``weak_type`` — from the abstract value;
- ``[lo, hi]`` — a sound worst-case interval for the value, seeded from
  per-input contracts (e.g. "``valid`` is a 0/1 mask") and propagated
  through arithmetic, reductions, ``dot_general`` (interval x
  contraction size), ``scan`` (closed-form linear accumulation growth),
  and ``psum`` (interval x mesh axis size);
- ``integral`` — whether the value is provably integer-valued, the bit
  that distinguishes a lossless int cast from one that discards
  fractional bilinear vote weights (the PR 3 bug class);
- ``clip`` — literal min/max clamp bounds the value just passed
  through, giving casts *clamp provenance*: a float->int store is
  sanctioned only when its operand was clamped to a range a quant
  policy declares (e.g. int16's (-32768, 32767));
- ``known`` — whether the interval came from real propagation rather
  than the dtype-range default, so overflow findings are proofs, not
  guesses about unconstrained inputs.

Control-flow and staging primitives (jit, scan, while, cond,
shard_map, pallas_call, custom_jvp/vjp) are recursed into with the
enclosing call stack recorded for finding provenance.  Pallas kernel
bodies are interpreted best-effort over a Ref environment (``get`` /
``swap`` / ``addupdate``).

Rules observe every equation via ``Rule.on_eqn`` and the program
outputs via ``Rule.on_outputs``; the interpreter itself raises nothing.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any, Callable, Sequence

import numpy as np

import jax
from jax._src import core as jcore
from jax._src import source_info_util

from repro.analysis.findings import Finding, Provenance

Inf = float("inf")


@dataclasses.dataclass(frozen=True)
class AbsVal:
    """Abstract state of one jaxpr value."""

    dtype: Any  # numpy dtype
    shape: tuple[int, ...] = ()
    weak_type: bool = False
    lo: float = -Inf
    hi: float = Inf
    integral: bool = False  # provably integer-valued
    known: bool = False  # interval from propagation, not the dtype default
    clip: tuple[float, float] | None = None  # literal clamp bounds just applied

    def with_(self, **kw: Any) -> "AbsVal":
        return dataclasses.replace(self, **kw)


def _is_int(dtype: Any) -> bool:
    return np.issubdtype(np.dtype(dtype), np.integer)


def _is_float(dtype: Any) -> bool:
    return np.issubdtype(np.dtype(dtype), np.floating)


def _is_bool(dtype: Any) -> bool:
    return np.dtype(dtype) == np.bool_


def int_range(dtype: Any) -> tuple[float, float]:
    info = np.iinfo(np.dtype(dtype))
    return float(info.min), float(info.max)


def _inner_aval(aval: Any) -> Any:
    # Pallas Refs wrap the array aval; state AbstractRef exposes inner_aval.
    return getattr(aval, "inner_aval", aval)


def absval_from_aval(aval: Any) -> AbsVal:
    aval = _inner_aval(aval)
    dtype = np.dtype(aval.dtype)
    shape = tuple(int(d) for d in getattr(aval, "shape", ()))
    weak = bool(getattr(aval, "weak_type", False))
    if _is_bool(dtype):
        return AbsVal(dtype, shape, weak, 0.0, 1.0, integral=True, known=True)
    if _is_int(dtype):
        lo, hi = int_range(dtype)
        # dtype-range default: sound but *not* "known" — overflow rules
        # must not claim proofs about unconstrained inputs.
        return AbsVal(dtype, shape, weak, lo, hi, integral=True, known=False)
    return AbsVal(dtype, shape, weak, -Inf, Inf, integral=False, known=False)


def absval_from_literal(val: Any) -> AbsVal:
    arr = np.asarray(val)
    dtype = arr.dtype
    weak = np.isscalar(val) or getattr(val, "weak_type", arr.ndim == 0)
    if arr.size == 0:
        return AbsVal(dtype, tuple(arr.shape), bool(weak), 0.0, 0.0, True, True)
    lo = float(np.min(arr))
    hi = float(np.max(arr))
    integral = _is_int(dtype) or _is_bool(dtype) or bool(
        np.all(np.isfinite(arr)) and np.all(arr == np.floor(arr))
    )
    return AbsVal(dtype, tuple(arr.shape), bool(weak), lo, hi, integral, True)


def _hull(vals: Sequence[AbsVal], dtype: Any, shape: tuple[int, ...]) -> AbsVal:
    lo = min((v.lo for v in vals), default=-Inf)
    hi = max((v.hi for v in vals), default=Inf)
    return AbsVal(
        np.dtype(dtype),
        shape,
        False,
        lo,
        hi,
        integral=all(v.integral for v in vals),
        known=all(v.known for v in vals),
    )


def _mul_bounds(a: AbsVal, b: AbsVal) -> tuple[float, float]:
    cands = []
    for x in (a.lo, a.hi):
        for y in (b.lo, b.hi):
            p = x * y
            if math.isnan(p):  # 0 * inf
                p = 0.0
            cands.append(p)
    return min(cands), max(cands)


class Rule:
    """Base class for lint rules driven by the interpreter."""

    rule_id = "rule"

    def on_eqn(self, ctx: "Context", eqn: Any, ins: list[AbsVal], outs: list[AbsVal]) -> None:
        pass

    def on_outputs(self, ctx: "Context", outs: list[AbsVal]) -> None:
        pass


@dataclasses.dataclass
class Context:
    """Mutable interpreter state shared with the rules."""

    entry: str
    rules: list[Rule]
    sanctioned_clips: frozenset[tuple[float, float]] = frozenset()
    findings: list[Finding] = dataclasses.field(default_factory=list)
    call_stack: list[str] = dataclasses.field(default_factory=list)
    # True while probing loop bodies for carry growth: rules are not fed,
    # so the same equation is reported once, from the final widest pass.
    muted: bool = False
    axis_sizes: dict[str, int] = dataclasses.field(default_factory=dict)
    # summary facts rules can publish (e.g. proved accumulator bounds)
    facts: dict[str, Any] = dataclasses.field(default_factory=dict)

    def provenance(self, eqn: Any) -> Provenance:
        try:
            src = source_info_util.summarize(eqn.source_info)
        except Exception:
            src = "<unknown>"
        try:
            pretty = str(eqn)
            pretty = pretty if len(pretty) <= 160 else pretty[:157] + "..."
        except Exception:
            pretty = ""
        return Provenance(
            primitive=eqn.primitive.name,
            source=src,
            call_stack=tuple(self.call_stack),
            eqn=pretty,
        )

    def report(self, eqn: Any, rule: str, kind: str, message: str, severity: str = "error") -> None:
        self.findings.append(
            Finding(
                rule=rule,
                kind=kind,
                entry=self.entry,
                message=message,
                provenance=self.provenance(eqn),
                severity=severity,
            )
        )


class DtypeFlowAnalyzer:
    """Interprets one jaxpr, feeding every equation to the rules."""

    def __init__(self, ctx: Context):
        self.ctx = ctx

    # -- driving ---------------------------------------------------------

    def run(self, closed_jaxpr: Any, in_absvals: Sequence[AbsVal]) -> list[AbsVal]:
        consts = [absval_from_literal(c) for c in closed_jaxpr.consts]
        outs = self.eval_jaxpr(closed_jaxpr.jaxpr, consts, list(in_absvals))
        for rule in self.ctx.rules:
            rule.on_outputs(self.ctx, outs)
        return outs

    def eval_jaxpr(self, jaxpr: Any, consts: list[AbsVal], args: list[AbsVal]) -> list[AbsVal]:
        env: dict[Any, AbsVal] = {}

        def read(atom: Any) -> AbsVal:
            if isinstance(atom, jcore.Literal):
                return absval_from_literal(atom.val)
            got = env.get(atom)
            if got is None:
                got = absval_from_aval(atom.aval)
            return got

        def write(var: Any, val: AbsVal) -> None:
            env[var] = val

        for v, c in zip(jaxpr.constvars, consts):
            write(v, c)
        for v, a in zip(jaxpr.invars, args):
            # Re-anchor the contract interval on the inner aval's dtype and
            # shape (shard_map narrows shapes; jit may differ in weak_type).
            inner = absval_from_aval(v.aval)
            write(
                v,
                inner.with_(
                    lo=a.lo, hi=a.hi, integral=a.integral, known=a.known, clip=a.clip
                ),
            )
        for eqn in jaxpr.eqns:
            ins = [read(x) for x in eqn.invars]
            outs = self.eval_eqn(eqn, ins)
            if not self.ctx.muted:
                for rule in self.ctx.rules:
                    rule.on_eqn(self.ctx, eqn, ins, outs)
            for var, out in zip(eqn.outvars, outs):
                write(var, out)
        return [read(x) for x in jaxpr.outvars]

    # -- equation dispatch ----------------------------------------------

    def eval_eqn(self, eqn: Any, ins: list[AbsVal]) -> list[AbsVal]:
        name = eqn.primitive.name
        handler = getattr(self, "_prim_" + name.replace("-", "_"), None)
        try:
            if handler is not None:
                outs = handler(eqn, ins)
                if outs is not None:
                    return outs
        except Exception:
            pass  # fall through to the conservative default
        return self.default_outs(eqn)

    def default_outs(self, eqn: Any) -> list[AbsVal]:
        return [absval_from_aval(v.aval) for v in eqn.outvars]

    def _out_aval(self, eqn: Any, i: int = 0) -> Any:
        return _inner_aval(eqn.outvars[i].aval)

    def _shaped(self, eqn: Any, base: AbsVal, i: int = 0, **kw: Any) -> list[AbsVal]:
        aval = self._out_aval(eqn, i)
        dtype = np.dtype(aval.dtype)
        integral = base.integral or _is_int(dtype) or _is_bool(dtype)
        out = AbsVal(
            dtype,
            tuple(int(d) for d in aval.shape),
            bool(getattr(aval, "weak_type", False)),
            base.lo,
            base.hi,
            integral=integral,
            known=base.known,
            clip=base.clip,
        )
        return [out.with_(**kw)] if kw else [out]

    # -- structural pass-throughs ---------------------------------------

    def _passthrough(self, eqn: Any, ins: list[AbsVal]) -> list[AbsVal]:
        return self._shaped(eqn, ins[0])

    _prim_broadcast_in_dim = _passthrough
    _prim_reshape = _passthrough
    _prim_transpose = _passthrough
    _prim_squeeze = _passthrough
    _prim_expand_dims = _passthrough
    _prim_rev = _passthrough
    _prim_slice = _passthrough
    _prim_copy = _passthrough
    _prim_stop_gradient = _passthrough
    _prim_gather = _passthrough
    _prim_dynamic_slice = _passthrough
    _prim_reduce_max = _passthrough
    _prim_reduce_min = _passthrough
    _prim_real = _passthrough
    _prim_device_put = _passthrough
    _prim_reduce_precision = _passthrough
    _prim_optimization_barrier = _passthrough

    def _prim_concatenate(self, eqn, ins):
        aval = self._out_aval(eqn)
        return [_hull(ins, aval.dtype, tuple(int(d) for d in aval.shape))]

    def _prim_pad(self, eqn, ins):
        aval = self._out_aval(eqn)
        return [_hull(ins[:2], aval.dtype, tuple(int(d) for d in aval.shape))]

    def _prim_select_n(self, eqn, ins):
        aval = self._out_aval(eqn)
        out = _hull(ins[1:], aval.dtype, tuple(int(d) for d in aval.shape))
        # a select between identically-clamped branches keeps clamp provenance
        clips = {v.clip for v in ins[1:]}
        if len(clips) == 1:
            out = out.with_(clip=clips.pop())
        return [out]

    def _prim_dynamic_update_slice(self, eqn, ins):
        aval = self._out_aval(eqn)
        return [_hull(ins[:2], aval.dtype, tuple(int(d) for d in aval.shape))]

    def _prim_sort(self, eqn, ins):
        return [self._shaped(eqn, v, i)[0] for i, v in enumerate(ins)]

    def _prim_iota(self, eqn, ins):
        aval = self._out_aval(eqn)
        dim = int(eqn.params.get("dimension", 0))
        n = int(aval.shape[dim]) if aval.shape else 1
        return self._shaped(
            eqn, AbsVal(aval.dtype, lo=0.0, hi=float(max(n - 1, 0)), integral=True, known=True)
        )

    # -- comparisons / logic --------------------------------------------

    def _bool_out(self, eqn, ins):
        base = AbsVal(np.dtype(np.bool_), lo=0.0, hi=1.0, integral=True, known=True)
        return self._shaped(eqn, base)

    _prim_eq = _bool_out
    _prim_ne = _bool_out
    _prim_lt = _bool_out
    _prim_le = _bool_out
    _prim_gt = _bool_out
    _prim_ge = _bool_out
    _prim_and = _bool_out
    _prim_or = _bool_out
    _prim_xor = _bool_out
    _prim_not = _bool_out
    _prim_is_finite = _bool_out
    _prim_reduce_and = _bool_out
    _prim_reduce_or = _bool_out

    # -- arithmetic ------------------------------------------------------

    def _prim_add(self, eqn, ins):
        a, b = ins
        return self._shaped(
            eqn,
            AbsVal(
                a.dtype,
                lo=a.lo + b.lo,
                hi=a.hi + b.hi,
                integral=a.integral and b.integral,
                known=a.known and b.known,
            ),
        )

    def _prim_sub(self, eqn, ins):
        a, b = ins
        return self._shaped(
            eqn,
            AbsVal(
                a.dtype,
                lo=a.lo - b.hi,
                hi=a.hi - b.lo,
                integral=a.integral and b.integral,
                known=a.known and b.known,
            ),
        )

    def _prim_mul(self, eqn, ins):
        a, b = ins
        lo, hi = _mul_bounds(a, b)
        return self._shaped(
            eqn,
            AbsVal(
                a.dtype,
                lo=lo,
                hi=hi,
                integral=a.integral and b.integral,
                known=a.known and b.known,
            ),
        )

    def _prim_div(self, eqn, ins):
        a, b = ins
        out_dtype = self._out_aval(eqn).dtype
        if b.lo > 0 or b.hi < 0:
            cands = [a.lo / b.lo, a.lo / b.hi, a.hi / b.lo, a.hi / b.hi]
            lo, hi = min(cands), max(cands)
        else:
            lo, hi = -Inf, Inf
        return self._shaped(
            eqn,
            AbsVal(out_dtype, lo=lo, hi=hi, integral=_is_int(out_dtype), known=a.known and b.known),
        )

    def _prim_rem(self, eqn, ins):
        a, b = ins
        mag = max(abs(b.lo), abs(b.hi))
        if not math.isfinite(mag):
            return self.default_outs(eqn)
        return self._shaped(
            eqn,
            AbsVal(a.dtype, lo=-mag, hi=mag, integral=a.integral and b.integral, known=a.known and b.known),
        )

    def _prim_neg(self, eqn, ins):
        a = ins[0]
        return self._shaped(eqn, a.with_(lo=-a.hi, hi=-a.lo, clip=None))

    def _prim_abs(self, eqn, ins):
        a = ins[0]
        lo = 0.0 if a.lo <= 0.0 <= a.hi else min(abs(a.lo), abs(a.hi))
        hi = max(abs(a.lo), abs(a.hi))
        return self._shaped(eqn, a.with_(lo=lo, hi=hi, clip=None))

    def _prim_sign(self, eqn, ins):
        return self._shaped(eqn, AbsVal(ins[0].dtype, lo=-1.0, hi=1.0, integral=True, known=True))

    def _prim_floor(self, eqn, ins):
        a = ins[0]
        lo = math.floor(a.lo) if math.isfinite(a.lo) else a.lo
        hi = math.floor(a.hi) if math.isfinite(a.hi) else a.hi
        return self._shaped(eqn, a.with_(lo=lo, hi=hi, integral=True, clip=None))

    def _prim_ceil(self, eqn, ins):
        a = ins[0]
        lo = math.ceil(a.lo) if math.isfinite(a.lo) else a.lo
        hi = math.ceil(a.hi) if math.isfinite(a.hi) else a.hi
        return self._shaped(eqn, a.with_(lo=lo, hi=hi, integral=True, clip=None))

    def _prim_round(self, eqn, ins):
        a = ins[0]
        lo = math.floor(a.lo) if math.isfinite(a.lo) else a.lo
        hi = math.ceil(a.hi) if math.isfinite(a.hi) else a.hi
        return self._shaped(eqn, a.with_(lo=lo, hi=hi, integral=True, clip=None))

    def _prim_nextafter(self, eqn, ins):
        return self._shaped(eqn, ins[0].with_(clip=None))

    def _prim_exp(self, eqn, ins):
        a = ins[0]
        lo = math.exp(a.lo) if a.lo < 700 else Inf
        hi = math.exp(a.hi) if a.hi < 700 else Inf
        return self._shaped(eqn, AbsVal(a.dtype, lo=lo, hi=hi, known=a.known))

    def _prim_sqrt(self, eqn, ins):
        a = ins[0]
        lo = math.sqrt(a.lo) if a.lo > 0 else 0.0
        hi = math.sqrt(a.hi) if math.isfinite(a.hi) and a.hi > 0 else (0.0 if a.hi <= 0 else Inf)
        return self._shaped(eqn, AbsVal(a.dtype, lo=lo, hi=hi, known=a.known))

    def _prim_logistic(self, eqn, ins):
        return self._shaped(eqn, AbsVal(ins[0].dtype, lo=0.0, hi=1.0, known=True))

    def _prim_tanh(self, eqn, ins):
        return self._shaped(eqn, AbsVal(ins[0].dtype, lo=-1.0, hi=1.0, known=True))

    def _prim_sin(self, eqn, ins):
        return self._shaped(eqn, AbsVal(ins[0].dtype, lo=-1.0, hi=1.0, known=True))

    _prim_cos = _prim_sin

    def _prim_integer_pow(self, eqn, ins):
        a = ins[0]
        y = int(eqn.params["y"])
        if y < 0 or not (math.isfinite(a.lo) and math.isfinite(a.hi)):
            return self.default_outs(eqn)
        cands = [a.lo**y, a.hi**y]
        lo, hi = min(cands), max(cands)
        if y % 2 == 0 and a.lo <= 0.0 <= a.hi:
            lo = 0.0
        return self._shaped(eqn, AbsVal(a.dtype, lo=lo, hi=hi, integral=a.integral, known=a.known))

    def _prim_square(self, eqn, ins):
        a = ins[0]
        if not (math.isfinite(a.lo) and math.isfinite(a.hi)):
            return self.default_outs(eqn)
        hi = max(a.lo * a.lo, a.hi * a.hi)
        lo = 0.0 if a.lo <= 0.0 <= a.hi else min(a.lo * a.lo, a.hi * a.hi)
        return self._shaped(eqn, AbsVal(a.dtype, lo=lo, hi=hi, integral=a.integral, known=a.known))

    # -- min/max and clamp provenance -----------------------------------

    @staticmethod
    def _literal_bound(v: AbsVal) -> float | None:
        # a literal (or literal-derived broadcast) has a degenerate interval
        if v.known and v.lo == v.hi and math.isfinite(v.lo):
            return v.lo
        return None

    def _prim_max(self, eqn, ins):
        a, b = ins
        out = AbsVal(
            a.dtype,
            lo=max(a.lo, b.lo),
            hi=max(a.hi, b.hi),
            integral=a.integral and b.integral,
            known=a.known and b.known,
        )
        # max(x, lit) starts a clamp chain: records the lower clamp bound
        clip = None
        for x, lit in ((a, self._literal_bound(b)), (b, self._literal_bound(a))):
            if lit is not None:
                prior_hi = x.clip[1] if x.clip else Inf
                clip = (lit, prior_hi)
        return self._shaped(eqn, out.with_(clip=clip))

    def _prim_min(self, eqn, ins):
        a, b = ins
        out = AbsVal(
            a.dtype,
            lo=min(a.lo, b.lo),
            hi=min(a.hi, b.hi),
            integral=a.integral and b.integral,
            known=a.known and b.known,
        )
        clip = None
        for x, lit in ((a, self._literal_bound(b)), (b, self._literal_bound(a))):
            if lit is not None:
                prior_lo = x.clip[0] if x.clip else -Inf
                clip = (prior_lo, lit)
        return self._shaped(eqn, out.with_(clip=clip))

    def _prim_clamp(self, eqn, ins):
        lo_v, x, hi_v = ins
        lo_lit = self._literal_bound(lo_v)
        hi_lit = self._literal_bound(hi_v)
        out = AbsVal(
            x.dtype,
            lo=max(x.lo, lo_v.lo),
            hi=min(x.hi, hi_v.hi),
            integral=x.integral and lo_v.integral and hi_v.integral,
            known=x.known and lo_v.known and hi_v.known,
        )
        clip = (lo_lit, hi_lit) if lo_lit is not None and hi_lit is not None else None
        return self._shaped(eqn, out.with_(clip=clip))

    # -- conversions -----------------------------------------------------

    def _prim_convert_element_type(self, eqn, ins):
        a = ins[0]
        aval = self._out_aval(eqn)
        nd = np.dtype(aval.dtype)
        if _is_bool(nd):
            out = AbsVal(nd, lo=0.0, hi=1.0, integral=True, known=True)
        elif _is_int(nd):
            rlo, rhi = int_range(nd)
            lo = math.floor(a.lo) if math.isfinite(a.lo) else a.lo
            hi = math.ceil(a.hi) if math.isfinite(a.hi) else a.hi
            if lo < rlo or hi > rhi:
                # wrap is possible; the stored state reflects the wrapped range
                lo, hi = rlo, rhi
            out = AbsVal(nd, lo=lo, hi=hi, integral=True, known=a.known, clip=a.clip)
        else:
            out = AbsVal(nd, lo=a.lo, hi=a.hi, integral=a.integral, known=a.known, clip=a.clip)
        return self._shaped(
            eqn, out, known=out.known, clip=out.clip, integral=out.integral,
            lo=out.lo, hi=out.hi,
        )

    # -- contractions / reductions --------------------------------------

    def _prim_dot_general(self, eqn, ins):
        a, b = ins
        (lhs_c, _rhs_c), _batch = eqn.params["dimension_numbers"]
        k = 1
        for d in lhs_c:
            k *= int(a.shape[d]) if a.shape else 1
        plo, phi = _mul_bounds(a, b)
        out_dtype = self._out_aval(eqn).dtype
        return self._shaped(
            eqn,
            AbsVal(
                out_dtype,
                lo=k * plo if math.isfinite(plo) else plo,
                hi=k * phi if math.isfinite(phi) else phi,
                integral=a.integral and b.integral,
                known=a.known and b.known,
            ),
        )

    def _prim_conv_general_dilated(self, eqn, ins):
        a, b = ins
        dn = eqn.params["dimension_numbers"]
        out_c_dim = dn.rhs_spec[0]
        k = 1
        for i, d in enumerate(b.shape):
            if i != out_c_dim:
                k *= int(d)
        plo, phi = _mul_bounds(a, b)
        out_dtype = self._out_aval(eqn).dtype
        return self._shaped(
            eqn,
            AbsVal(
                out_dtype,
                lo=k * plo if math.isfinite(plo) else plo,
                hi=k * phi if math.isfinite(phi) else phi,
                integral=a.integral and b.integral,
                known=a.known and b.known,
            ),
        )

    def _prim_reduce_sum(self, eqn, ins):
        a = ins[0]
        k = 1
        for d in eqn.params["axes"]:
            k *= int(a.shape[d]) if a.shape else 1
        return self._shaped(
            eqn,
            AbsVal(
                a.dtype,
                lo=k * a.lo if math.isfinite(a.lo) else a.lo,
                hi=k * a.hi if math.isfinite(a.hi) else a.hi,
                integral=a.integral,
                known=a.known,
            ),
        )

    def _prim_cumsum(self, eqn, ins):
        a = ins[0]
        axis = int(eqn.params.get("axis", 0))
        n = int(a.shape[axis]) if a.shape else 1
        lo = min(a.lo, n * a.lo) if math.isfinite(a.lo) else a.lo
        hi = max(a.hi, n * a.hi) if math.isfinite(a.hi) else a.hi
        return self._shaped(eqn, a.with_(lo=lo, hi=hi, clip=None))

    def _prim_argmax(self, eqn, ins):
        a = ins[0]
        n = 1
        for d in eqn.params.get("axes", ()):
            n *= int(a.shape[d]) if a.shape else 1
        out_dtype = self._out_aval(eqn).dtype
        return self._shaped(
            eqn, AbsVal(out_dtype, lo=0.0, hi=float(max(n - 1, 0)), integral=True, known=True)
        )

    _prim_argmin = _prim_argmax

    def _prim_scatter_add(self, eqn, ins):
        tgt, _idx, upd = ins
        n = 1
        for d in upd.shape:
            n *= int(d)
        lo = tgt.lo + n * min(0.0, upd.lo)
        hi = tgt.hi + n * max(0.0, upd.hi)
        if not math.isfinite(upd.lo):
            lo = -Inf
        if not math.isfinite(upd.hi):
            hi = Inf
        return self._shaped(
            eqn,
            AbsVal(
                tgt.dtype,
                lo=lo,
                hi=hi,
                integral=tgt.integral and upd.integral,
                known=tgt.known and upd.known,
            ),
        )

    def _prim_scatter(self, eqn, ins):
        aval = self._out_aval(eqn)
        return [_hull([ins[0], ins[2]], aval.dtype, tuple(int(d) for d in aval.shape))]

    # -- collectives -----------------------------------------------------

    def _axis_prod(self, eqn) -> int:
        n = 1
        for ax in eqn.params.get("axes", eqn.params.get("axis_name", ())):
            n *= int(self.ctx.axis_sizes.get(ax, 1))
        return n

    def _prim_psum(self, eqn, ins):
        n = self._axis_prod(eqn)
        outs = []
        for i, a in enumerate(ins):
            lo = n * a.lo if math.isfinite(a.lo) else a.lo
            hi = n * a.hi if math.isfinite(a.hi) else a.hi
            outs.append(self._shaped(eqn, a.with_(lo=lo, hi=hi, clip=None), i)[0])
        return outs

    def _prim_pmax(self, eqn, ins):
        return [self._shaped(eqn, a, i)[0] for i, a in enumerate(ins)]

    _prim_pmin = _prim_pmax
    _prim_all_gather = _prim_pmax

    # -- higher-order / staging -----------------------------------------

    def _recurse(self, tag: str, jaxpr: Any, consts: list[AbsVal], args: list[AbsVal]) -> list[AbsVal]:
        self.ctx.call_stack.append(tag)
        try:
            return self.eval_jaxpr(jaxpr, consts, args)
        finally:
            self.ctx.call_stack.pop()

    def _prim_jit(self, eqn, ins):
        closed = eqn.params["jaxpr"]
        name = eqn.params.get("name", "jit")
        consts = [absval_from_literal(c) for c in closed.consts]
        return self._recurse(f"jit:{name}", closed.jaxpr, consts, ins)

    def _prim_closed_call(self, eqn, ins):
        closed = eqn.params["call_jaxpr"]
        consts = [absval_from_literal(c) for c in closed.consts]
        return self._recurse("closed_call", closed.jaxpr, consts, ins)

    def _prim_custom_jvp_call(self, eqn, ins):
        closed = eqn.params["call_jaxpr"]
        consts = [absval_from_literal(c) for c in closed.consts]
        return self._recurse("custom_jvp", closed.jaxpr, consts, ins)

    def _prim_custom_vjp_call(self, eqn, ins):
        closed = eqn.params["call_jaxpr"]
        consts = [absval_from_literal(c) for c in closed.consts]
        return self._recurse("custom_vjp", closed.jaxpr, consts, ins)

    def _prim_remat2(self, eqn, ins):
        jaxpr = eqn.params["jaxpr"]
        return self._recurse("remat", jaxpr, [], ins)

    def _prim_cond(self, eqn, ins):
        branches = eqn.params["branches"]
        ops = ins[1:]
        branch_outs = []
        for i, br in enumerate(branches):
            consts = [absval_from_literal(c) for c in br.consts]
            branch_outs.append(self._recurse(f"cond:branch{i}", br.jaxpr, consts, list(ops)))
        outs = []
        for i in range(len(eqn.outvars)):
            aval = self._out_aval(eqn, i)
            outs.append(
                _hull([bo[i] for bo in branch_outs], aval.dtype, tuple(int(d) for d in aval.shape))
            )
        return outs

    def _prim_while(self, eqn, ins):
        cn = eqn.params["cond_nconsts"]
        bn = eqn.params["body_nconsts"]
        body = eqn.params["body_jaxpr"]
        body_consts = ins[cn : cn + bn]
        carry = list(ins[cn + bn :])
        closed_consts = [absval_from_literal(c) for c in body.consts]

        def body(c: list[AbsVal]) -> list[AbsVal]:
            return self._recurse("while:body", body.jaxpr, closed_consts, body_consts + c)

        was_muted = self.ctx.muted
        self.ctx.muted = True
        try:
            carry = self._fixpoint_carry("while:body", body, carry, n_iters=None)
        finally:
            self.ctx.muted = was_muted
        final = body(carry)  # one unmuted pass at the widest carry state
        carry = [
            c.with_(lo=min(c.lo, f.lo), hi=max(c.hi, f.hi))
            for c, f in zip(carry, final)
        ]
        return [self._shaped(eqn, c, i)[0] for i, c in enumerate(carry)]

    def _prim_scan(self, eqn, ins):
        params = eqn.params
        num_consts = params["num_consts"]
        num_carry = params["num_carry"]
        length = int(params["length"])
        closed = params["jaxpr"]
        consts = ins[:num_consts]
        carry0 = list(ins[num_consts : num_consts + num_carry])
        xs = ins[num_consts + num_carry :]
        closed_consts = [absval_from_literal(c) for c in closed.consts]

        # per-iteration slices of xs keep the same interval
        def body(carry: list[AbsVal]) -> list[AbsVal]:
            outs = self._recurse(
                "scan:body", closed.jaxpr, closed_consts, consts + carry + list(xs)
            )
            return outs

        was_muted = self.ctx.muted
        self.ctx.muted = True
        try:
            carry = self._scan_carry(body, carry0, length, num_carry)
        finally:
            self.ctx.muted = was_muted
        final = body(carry)
        carry_out = final[:num_carry]
        ys = final[num_carry:]
        outs = []
        for i in range(len(eqn.outvars)):
            src = carry_out[i] if i < num_carry else ys[i - num_carry]
            outs.append(self._shaped(eqn, src, i)[0])
        return outs

    def _scan_carry(
        self,
        body: Callable[[list[AbsVal]], list[AbsVal]],
        carry0: list[AbsVal],
        length: int,
        num_carry: int,
    ) -> list[AbsVal]:
        """Bound the scan carry after ``length`` iterations.

        Detects linear accumulation: if one body application grows each
        carry interval by a constant increment (d_lo, d_hi) and a second
        application grows it by the same increment, the closed form
        ``carry0 + length * d`` bounds the final carry — this is what
        proves "C frames x E events x max vote <= int32 max" without
        unrolling C iterations.  Nonlinear growth falls back to a short
        fixpoint iteration and then widens to the dtype default.
        """
        if length <= 0 or num_carry == 0:
            return carry0
        c1 = body(carry0)[:num_carry]
        c2 = body(c1)[:num_carry]
        grown: list[AbsVal] = []
        linear = True
        for a0, a1, a2 in zip(carry0, c1, c2):
            d_lo1, d_hi1 = a1.lo - a0.lo, a1.hi - a0.hi
            d_lo2, d_hi2 = a2.lo - a1.lo, a2.hi - a1.hi
            finite = all(
                math.isfinite(x) for x in (d_lo1, d_hi1, d_lo2, d_hi2)
            )
            if finite and math.isclose(d_lo1, d_lo2, abs_tol=1e-6) and math.isclose(
                d_hi1, d_hi2, abs_tol=1e-6
            ):
                grown.append(
                    a0.with_(
                        lo=min(a0.lo, a0.lo + length * d_lo1),
                        hi=max(a0.hi, a0.hi + length * d_hi1),
                    )
                )
            else:
                linear = False
                grown.append(a0)
        if linear:
            return grown
        return self._fixpoint_carry("scan", body, carry0, n_iters=length, num_carry=num_carry)

    def _fixpoint_carry(
        self,
        tag: str,
        body: Callable[[list[AbsVal]], list[AbsVal]],
        carry0: list[AbsVal],
        n_iters: int | None,
        num_carry: int | None = None,
    ) -> list[AbsVal]:
        carry = carry0
        max_steps = min(n_iters, 32) if n_iters is not None else 32
        for _ in range(max_steps):
            nxt = body(carry)
            if num_carry is not None:
                nxt = nxt[:num_carry]
            nxt = [
                c.with_(lo=min(c.lo, n.lo), hi=max(c.hi, n.hi), integral=c.integral and n.integral)
                for c, n in zip(carry, nxt)
            ]
            if all(n.lo == c.lo and n.hi == c.hi for c, n in zip(carry, nxt)):
                return nxt
            carry = nxt
        if n_iters is not None and n_iters <= 32:
            return carry
        # did not converge within budget: widen to the dtype default
        return [
            absval_from_aval_like(c).with_(integral=c.integral) for c in carry
        ]

    def _prim_shard_map(self, eqn, ins):
        jaxpr = eqn.params["jaxpr"]  # raw Jaxpr
        mesh = eqn.params.get("mesh")
        if mesh is not None:
            for name, size in zip(mesh.axis_names, mesh.devices.shape):
                self.ctx.axis_sizes[str(name)] = int(size)
        return self._recurse("shard_map", jaxpr, [], ins)

    def _prim_pallas_call(self, eqn, ins):
        jaxpr = eqn.params["jaxpr"]  # raw Jaxpr over Refs
        n_in = len(ins)
        refs: dict[Any, AbsVal] = {}
        for i, v in enumerate(jaxpr.invars):
            if i < n_in:
                base = ins[i]
                inner = absval_from_aval(v.aval)
                refs[v] = inner.with_(
                    lo=base.lo, hi=base.hi, integral=base.integral, known=base.known
                )
            else:
                # output refs start zero-initialized or undefined; assume 0
                inner = absval_from_aval(v.aval)
                refs[v] = inner.with_(lo=0.0, hi=0.0, integral=True, known=True)
        self.ctx.call_stack.append("pallas_call")
        try:
            self._eval_pallas_body(jaxpr, refs)
        finally:
            self.ctx.call_stack.pop()
        outs = []
        out_refs = jaxpr.invars[n_in:]
        for i in range(len(eqn.outvars)):
            if i < len(out_refs):
                st = refs[out_refs[i]]
                outs.append(self._shaped(eqn, st, i)[0])
            else:
                outs.append(absval_from_aval(self._out_aval(eqn, i)))
        return outs

    def _eval_pallas_body(self, jaxpr: Any, refs: dict[Any, AbsVal]) -> None:
        """Best-effort walk of a Pallas kernel body over a Ref env.

        ``get`` reads the ref state, ``swap`` / ``addupdate`` widen it
        (the grid may revisit a block arbitrarily often, so stores are
        treated as accumulating into an unknown number of slots).  All
        equations are still fed to the rules, so a fractional float->int
        cast inside a kernel body is flagged exactly like one outside.
        """
        env: dict[Any, AbsVal] = dict(refs)

        def read(atom: Any) -> AbsVal:
            if isinstance(atom, jcore.Literal):
                return absval_from_literal(atom.val)
            got = env.get(atom)
            if got is None:
                got = absval_from_aval(atom.aval)
            return got

        for eqn in jaxpr.eqns:
            name = eqn.primitive.name
            ins = [read(x) for x in eqn.invars]
            if name == "get":
                ref_var = eqn.invars[0]
                st = env.get(ref_var, absval_from_aval(ref_var.aval))
                outs = self._shaped(eqn, st)
            elif name in ("swap", "masked_swap"):
                ref_var = eqn.invars[0]
                st = env.get(ref_var, absval_from_aval(ref_var.aval))
                new = ins[1]
                merged = st.with_(
                    lo=min(st.lo, new.lo),
                    hi=max(st.hi, new.hi),
                    integral=st.integral and new.integral,
                    known=st.known and new.known,
                )
                env[ref_var] = merged
                outs = self._shaped(eqn, st) if eqn.outvars else []
            elif name in ("addupdate", "masked_addupdate"):
                ref_var = eqn.invars[0]
                st = env.get(ref_var, absval_from_aval(ref_var.aval))
                new = ins[1]
                if new.lo == 0.0 and new.hi == 0.0:
                    merged = st
                else:
                    # unknown grid revisit count: any nonzero accumulation
                    # widens toward the dtype default
                    widened = absval_from_aval(_inner_aval(ref_var.aval))
                    merged = widened.with_(integral=st.integral and new.integral)
                env[ref_var] = merged
                outs = []
            elif name == "program_id":
                outs = self._shaped(
                    eqn, AbsVal(np.dtype(np.int32), lo=0.0, hi=Inf, integral=True, known=False)
                )
            elif name == "cond":
                outs = self._prim_cond(eqn, ins)
            else:
                outs = self.eval_eqn(eqn, ins)
            for rule in self.ctx.rules:
                rule.on_eqn(self.ctx, eqn, ins, outs)
            for var, out in zip(eqn.outvars, outs):
                env[var] = out


def absval_from_aval_like(v: AbsVal) -> AbsVal:
    dtype = np.dtype(v.dtype)
    if _is_bool(dtype):
        return AbsVal(dtype, v.shape, v.weak_type, 0.0, 1.0, True, True)
    if _is_int(dtype):
        lo, hi = int_range(dtype)
        return AbsVal(dtype, v.shape, v.weak_type, lo, hi, True, False)
    return AbsVal(dtype, v.shape, v.weak_type, -Inf, Inf, False, False)


def analyze_program(
    fn: Callable[..., Any],
    args: Sequence[Any],
    contracts: Sequence[AbsVal] | None,
    *,
    entry: str,
    rules: list[Rule],
    sanctioned_clips: frozenset[tuple[float, float]] = frozenset(),
) -> Context:
    """Trace ``fn(*args)`` (args are ShapeDtypeStructs) and run the rules.

    ``contracts`` — one AbsVal per *flattened* input leaf, or ``None``
    to use the dtype defaults.  Returns the populated :class:`Context`.
    """
    closed = jax.make_jaxpr(fn)(*args)
    leaves = jax.tree_util.tree_leaves(tuple(args))
    if contracts is None:
        in_absvals = [
            absval_from_aval(jcore.ShapedArray(l.shape, l.dtype)) for l in leaves
        ]
    else:
        if len(contracts) != len(closed.jaxpr.invars):
            raise ValueError(
                f"{entry}: {len(contracts)} contracts for {len(closed.jaxpr.invars)} inputs"
            )
        in_absvals = list(contracts)
    ctx = Context(entry=entry, rules=rules, sanctioned_clips=sanctioned_clips)
    DtypeFlowAnalyzer(ctx).run(closed, in_absvals)
    return ctx
