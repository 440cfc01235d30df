"""Structural participation analysis — backward taint over the jaxpr.

Why this exists (see EXPERIMENTS.md §Paper-validation / FT):  the paper's
definition of *uncritical* is "no impact on the output", measured as a zero
derivative.  Enzyme computes that derivative in floating point, so an element
whose influence cancels *exactly* in real arithmetic (e.g. NPB-FT's checksum,
whose sampling comb aliases most frequency lattice points to an exactly-zero
Jacobian entry) still shows a ~1e-16 residue and is reported critical.  Every
number in the paper's Table II is therefore a **participation** result: an
element is critical iff the remaining computation *reads* it (transitively,
before overwriting it).

``participation(fn, state)`` computes exactly that, element-granular, in one
backward sweep over the jaxpr of ``fn``:

- Seed every output element as tainted.
- Walk equations in reverse; each primitive maps output taint to input taint.
- **Write-before-read is exact**: ``scatter``/``dynamic_update_slice`` clear
  the taint of the overwritten window of the operand — the paper's central
  mechanism ("written but not read ⇒ uncritical").
- For linear structural primitives (slice/pad/concat/reshape/broadcast/
  reduce_sum/cumsum/gather/scatter/dynamic slicing) taint is propagated
  through the primitive's own transpose (vjp) with a nonnegative 0/1
  cotangent: coefficients are 0/1 so sums of nonnegatives cannot cancel —
  the propagation is *exact*, not conservative.
- Value-coupling primitives (dot_general, fft, reductions, sort, cumprod)
  use structural rules: any tainted output along the coupled axes taints all
  coupled inputs.  This is deliberately value-independent — "multiplied by a
  weight that happens to be zero" still counts as participation.
- Control flow: ``cond`` unions branches; ``scan``/``while`` run the body
  rule to an OR-fixpoint on the carry (monotone on a finite lattice, with a
  saturating cap); predicates/indices are control state → fully tainted.
- Unknown primitives fall back to any→all (sound over-approximation, never
  under-reports criticality).

Relationship to the AD engine (criticality.py):

    grad-critical  ⊆  participation-critical   (exact arithmetic)

``scrutinize`` (vjp probes) is the paper's *method* and the sharper mask;
``participation`` is the paper's *reported semantics* and is immune to both
exact-cancellation (FT) and probe-point nonlinearity, so it is the safe
default for production checkpoint dropping.  Both are validated against each
other and against the paper in tests/ and EXPERIMENTS.md.
"""

from __future__ import annotations

import contextlib
import functools
import time
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.extend import core as jex_core

from repro import obs as obs_mod
from repro.core.bitset import BitMask
from repro.core.criticality import (CriticalityReport, LeafReport, _path_str,
                                    traced_step)
from repro.core.policy import LeafPolicy, ScrutinyConfig
from repro.core.regions import RegionTable

Literal = jex_core.Literal

# Iteration cap for scan/while carry fixpoints before saturating to all-True.
_FIXPOINT_CAP = 128


def _shape(v) -> Tuple[int, ...]:
    return tuple(getattr(v.aval, "shape", ()))


def _is_drop(v) -> bool:
    return type(v).__name__ == "DropVar"


def _zeros(v) -> np.ndarray:
    return np.zeros(_shape(v), dtype=bool)


def _full(v, value: bool) -> np.ndarray:
    return np.full(_shape(v), value, dtype=bool)


def _size(shape: Tuple[int, ...]) -> int:
    return int(np.prod(shape)) if shape else 1


def _pack(t: np.ndarray) -> BitMask:
    """Shaped bool taint → flat bit-packed lattice element."""
    return BitMask.from_bool(np.asarray(t, dtype=bool).reshape(-1))


def _unpack(bm: BitMask, shape: Tuple[int, ...]) -> np.ndarray:
    """Flat bit-packed lattice element → shaped bool taint (for rules)."""
    return bm.to_bool().reshape(shape)


# --------------------------------------------------------------------------
# Forward concrete evaluation (records every intermediate so backward rules
# can resolve gather/scatter/dynamic-slice indices exactly).
# --------------------------------------------------------------------------

# Call-like primitives we recurse into (1:1 invar mapping) so inner
# intermediates land in the same env.
_RECURSE_CALLS = {
    "jit",  # jax>=0.7 name for the pjit primitive
    "pjit",
    "closed_call",
    "core_call",
    "remat",
    "remat2",
    "checkpoint",
    "custom_jvp_call",
    "custom_vjp_call",
    "custom_vjp_call_jaxpr",
}


def _inner_closed(eqn) -> Optional[jex_core.ClosedJaxpr]:
    for key in ("jaxpr", "call_jaxpr", "fun_jaxpr"):
        sub = eqn.params.get(key)
        if sub is None:
            continue
        if isinstance(sub, jex_core.ClosedJaxpr):
            return sub
        if isinstance(sub, jex_core.Jaxpr):
            return jex_core.ClosedJaxpr(sub, ())
    return None


def _forward_env(jaxpr: jex_core.Jaxpr, consts, args, env: Dict[Any, Any]) -> List[Any]:
    """Evaluate ``jaxpr`` eagerly, recording every var's value in ``env``."""
    for v, c in zip(jaxpr.constvars, consts):
        env[v] = c
    for v, a in zip(jaxpr.invars, args):
        env[v] = a

    def read(v):
        return v.val if isinstance(v, Literal) else env[v]

    for eqn in jaxpr.eqns:
        invals = [read(v) for v in eqn.invars]
        sub = _inner_closed(eqn) if eqn.primitive.name in _RECURSE_CALLS else None
        if sub is not None and len(sub.jaxpr.invars) == len(invals):
            outvals = _forward_env(sub.jaxpr, sub.consts, invals, env)
        else:
            outvals = eqn.primitive.bind(*invals, **eqn.params)
            if not eqn.primitive.multiple_results:
                outvals = [outvals]
        for v, val in zip(eqn.outvars, outvals):
            if not _is_drop(v):
                env[v] = val
    return [read(v) for v in jaxpr.outvars]


def _concrete(var, env: Optional[Dict]) -> Optional[Any]:
    if isinstance(var, Literal):
        return var.val
    if env is None:
        return None
    return env.get(var)


# --------------------------------------------------------------------------
# Primitive rules
# --------------------------------------------------------------------------

# Elementwise: input taint = output taint (shapes equal in jaxprs; lax
# inserts explicit broadcast_in_dim).  Covers unary + binary + select/clamp.
_ELEMENTWISE = {
    "abs", "acos", "acosh", "asin", "asinh", "atan", "atanh", "cbrt", "ceil",
    "cos", "cosh", "digamma", "erf", "erf_inv", "erfc", "exp", "exp2",
    "expm1", "floor", "imag", "is_finite", "lgamma", "log", "log1p",
    "logistic", "neg", "not", "population_count", "clz", "real", "round",
    "rsqrt", "sign", "sin", "sinh", "sqrt", "square", "tan", "tanh",
    "conj", "copy", "convert_element_type", "stop_gradient",
    "reduce_precision", "integer_pow", "device_put",
    # binary
    "add", "sub", "mul", "div", "rem", "max", "min", "pow", "atan2", "and",
    "or", "xor", "shift_left", "shift_right_logical",
    "shift_right_arithmetic", "eq", "ne", "ge", "gt", "le", "lt", "complex",
    "nextafter", "igamma", "igammac",
    # n-ary elementwise
    "select_n", "clamp",
}

# Structural linear primitives propagated exactly through their transpose
# with a nonnegative 0/1 cotangent (coefficients 0/1 ⇒ no cancellation).
_VJP_STRUCTURAL = {
    "reshape", "transpose", "slice", "pad", "concatenate", "rev", "squeeze",
    "broadcast_in_dim", "reduce_sum", "cumsum", "split", "expand_dims",
}

# axis-coupling reductions: output taint broadcasts back over reduced axes.
_REDUCE_AXES = {"reduce_max", "reduce_min", "reduce_prod", "reduce_and",
                "reduce_or", "reduce_xor", "argmax", "argmin"}

_CUM_SUFFIX = {"cumprod", "cummax", "cummin", "cumlogsumexp"}


def _unflatten_outs(eqn, taint_map) -> List[np.ndarray]:
    outs = []
    for v in eqn.outvars:
        if _is_drop(v):
            outs.append(_zeros(v))
        else:
            outs.append(taint_map.get(v, _zeros(v)))
    return outs


def _vjp_structural(eqn, outs: List[np.ndarray]) -> Optional[List[np.ndarray]]:
    """Exact taint transpose for linear 0/1-coefficient primitives."""
    in_avals = [v.aval for v in eqn.invars]

    def f(*data):
        return eqn.primitive.bind(*data, **eqn.params)

    primals = [jnp.zeros(a.shape, jnp.float32) for a in in_avals]
    try:
        out_sd, vjp_fn = jax.vjp(f, *primals)
    except Exception:
        return None
    cts = _as_cotangents(out_sd, outs, eqn)
    grads = vjp_fn(cts)
    return [np.asarray(g) != 0.0 for g in grads]


def _as_cotangents(out_sd, outs, eqn):
    if eqn.primitive.multiple_results:
        return [jnp.asarray(t, jnp.float32) for t in outs]
    return jnp.asarray(outs[0], jnp.float32)


def _indexed_vjp(eqn, outs, env, public_fn, index_pos: Sequence[int],
                 data_pos: Sequence[int], call_builder) -> Optional[List[Optional[np.ndarray]]]:
    """Taint transpose for gather/scatter/dynamic ops with concrete indices.

    ``call_builder(idx_vals)(*float_data_args)`` must reproduce the op via
    the public lax API (dtype-agnostic).  Index operands become fully
    tainted (they are control state selecting which elements are read).
    """
    idx_vals = []
    for i in index_pos:
        val = _concrete(eqn.invars[i], env)
        if val is None:
            return None
        idx_vals.append(val)
    f = call_builder(idx_vals)
    primals = [jnp.zeros(eqn.invars[i].aval.shape, jnp.float32) for i in data_pos]
    try:
        out_sd, vjp_fn = jax.vjp(f, *primals)
    except Exception:
        return None
    cts = _as_cotangents(out_sd, outs, eqn)
    grads = vjp_fn(cts)
    result: List[Optional[np.ndarray]] = [None] * len(eqn.invars)
    any_out = any(t.any() for t in outs)
    for i, g in zip(data_pos, grads):
        result[i] = np.asarray(g) != 0.0
    for i in index_pos:
        result[i] = _full(eqn.invars[i], any_out)
    return result


def _rule_dot_general(eqn, outs):
    (lc, rc), (lb, rb) = eqn.params["dimension_numbers"]
    lhs, rhs = eqn.invars
    lsh, rsh = _shape(lhs), _shape(rhs)
    out_t = outs[0]
    lfree = [d for d in range(len(lsh)) if d not in lc and d not in lb]
    rfree = [d for d in range(len(rsh)) if d not in rc and d not in rb]
    nb, nlf, nrf = len(lb), len(lfree), len(rfree)

    def side(free, contract, batch, reduce_axes, shape):
        t = out_t.any(axis=tuple(reduce_axes)) if reduce_axes else out_t
        # t axes: [batch..., own_free...]; append contract dims then permute.
        t = t.reshape(t.shape + (1,) * len(contract))
        t = np.broadcast_to(t, t.shape[: nb + len(free)] + tuple(shape[c] for c in contract))
        src_order = list(batch) + list(free) + list(contract)
        perm = np.argsort(src_order)
        return np.transpose(t, perm)

    lhs_t = side(lfree, lc, lb, range(nb + nlf, nb + nlf + nrf), lsh)
    rhs_t = side(rfree, rc, rb, range(nb, nb + nlf), rsh)
    return [lhs_t, rhs_t]


def _rule_fft(eqn, outs):
    k = len(eqn.params["fft_lengths"])
    in_shape = _shape(eqn.invars[0])
    axes = tuple(range(len(in_shape) - k, len(in_shape)))
    t = outs[0].any(axis=axes, keepdims=True)
    return [np.broadcast_to(t, in_shape)]


def _rule_gather(eqn, outs, env):
    p = eqn.params

    def build(idx_vals):
        (idx,) = idx_vals

        def f(operand):
            return lax.gather(
                operand, idx, dimension_numbers=p["dimension_numbers"],
                slice_sizes=p["slice_sizes"], unique_indices=p["unique_indices"],
                indices_are_sorted=p["indices_are_sorted"], mode=p["mode"])

        return f

    return _indexed_vjp(eqn, outs, env, lax.gather, index_pos=(1,),
                        data_pos=(0,), call_builder=build)


def _rule_scatter(eqn, outs, env, variant: str):
    p = eqn.params
    # replace-scatter clears the overwritten window (write-before-read);
    # accumulating variants still read the operand there.
    fn = lax.scatter if variant == "scatter" else lax.scatter_add

    def build(idx_vals):
        (idx,) = idx_vals

        def f(operand, updates):
            return fn(operand, idx, updates,
                      dimension_numbers=p["dimension_numbers"],
                      indices_are_sorted=p["indices_are_sorted"],
                      unique_indices=p["unique_indices"], mode=p["mode"])

        return f

    res = _indexed_vjp(eqn, outs, env, fn, index_pos=(1,), data_pos=(0, 2),
                       call_builder=build)
    if res is None:
        # No concrete indices: keep the operand taint everywhere (we cannot
        # prove any window overwritten — sound), updates/indices unknown.
        any_out = outs[0].any()
        return [outs[0], _full(eqn.invars[1], any_out), _full(eqn.invars[2], any_out)]
    return res


def _rule_dynamic_slice(eqn, outs, env):
    p = eqn.params

    def build(idx_vals):
        starts = [int(np.asarray(s)) for s in idx_vals]

        def f(operand):
            return lax.dynamic_slice(operand, starts, p["slice_sizes"])

        return f

    return _indexed_vjp(eqn, outs, env, lax.dynamic_slice,
                        index_pos=tuple(range(1, len(eqn.invars))),
                        data_pos=(0,), call_builder=build)


def _rule_dynamic_update_slice(eqn, outs, env):
    def build(idx_vals):
        starts = [int(np.asarray(s)) for s in idx_vals]

        def f(operand, update):
            return lax.dynamic_update_slice(operand, update, starts)

        return f

    res = _indexed_vjp(eqn, outs, env, lax.dynamic_update_slice,
                       index_pos=tuple(range(2, len(eqn.invars))),
                       data_pos=(0, 1), call_builder=build)
    if res is None:
        # Unknown window: keep operand taint (sound), update fully tainted.
        any_out = outs[0].any()
        starts_t = [_full(v, any_out) for v in eqn.invars[2:]]
        return [outs[0], _full(eqn.invars[1], any_out)] + starts_t
    return res


def _rule_cum_suffix(eqn, outs):
    axis, reverse = eqn.params["axis"], eqn.params["reverse"]
    t = outs[0]
    if reverse:
        t = np.logical_or.accumulate(t, axis=axis)
    else:
        t = np.flip(np.logical_or.accumulate(np.flip(t, axis), axis=axis), axis)
    return [t]


def _rule_sort(eqn, outs):
    dim = eqn.params["dimension"]
    any_t = np.zeros(outs[0].shape, bool)
    for t in outs:
        any_t |= t
    t = np.broadcast_to(any_t.any(axis=dim, keepdims=True), any_t.shape)
    return [t.copy() for _ in eqn.invars]


def _sub_env(inner_jaxpr, inner_consts, const_invar_pairs, outer_env) -> Dict:
    """Env for a loop/branch body: its own consts + the eqn operands that are
    loop-invariant (scan/while consts, cond operands) resolved from the outer
    env — this keeps hoisted scatter/gather indices concrete inside bodies."""
    env: Dict[Any, Any] = {}
    for v, c in zip(inner_jaxpr.constvars, inner_consts):
        env[v] = c
    for inner_v, outer_v in const_invar_pairs:
        val = _concrete(outer_v, outer_env)
        if val is not None:
            env[inner_v] = val
    return env


def _rule_scan(eqn, outs, bw, outer_env):
    p = eqn.params
    body: jex_core.ClosedJaxpr = p["jaxpr"]
    nc, ncar = p["num_consts"], p["num_carry"]
    length = int(p["length"])
    carry_shapes = [t.shape for t in outs[:ncar]]
    # Carries and accumulators live bit-packed: the OR-joins and the
    # convergence test each iteration are word ops, not bool-array scans.
    carry_t = [_pack(t) for t in outs[:ncar]]
    ys_slice_t = [t.any(axis=0) if t.ndim else t for t in outs[ncar:]]

    n_in = len(body.jaxpr.invars)
    const_shapes = [_shape(body.jaxpr.invars[i]) for i in range(nc)]
    xs_shapes = [_shape(body.jaxpr.invars[i]) for i in range(nc + ncar, n_in)]
    consts_acc = [BitMask.zeros(_size(s)) for s in const_shapes]
    xs_acc = [BitMask.zeros(_size(s)) for s in xs_shapes]
    benv = _sub_env(body.jaxpr, body.consts,
                    list(zip(body.jaxpr.invars[:nc], eqn.invars[:nc])),
                    outer_env)

    converged = False
    for it in range(min(length, _FIXPOINT_CAP)):
        body_outs = [_unpack(c, s) for c, s in zip(carry_t, carry_shapes)] + \
            [np.asarray(t) for t in ys_slice_t]
        ins_t = bw(body.jaxpr, body.consts, body_outs, benv)
        for j in range(nc):
            consts_acc[j].ior(_pack(ins_t[j]))
        for j, t in enumerate(ins_t[nc + ncar:]):
            xs_acc[j].ior(_pack(t))
        new_carry = [c | _pack(t)
                     for c, t in zip(carry_t, ins_t[nc:nc + ncar])]
        if it > 0 and all(a == b for a, b in zip(new_carry, carry_t)):
            carry_t = new_carry
            converged = True
            break
        carry_t = new_carry
    if not converged and length > _FIXPOINT_CAP:
        carry_t = [BitMask.full(c.n) for c in carry_t]  # saturate (sound)
        consts_acc = [BitMask.full(c.n) for c in consts_acc]
        xs_acc = [BitMask.full(c.n) for c in xs_acc]

    xs_t = []
    for j, v in enumerate(eqn.invars[nc + ncar:]):
        xs_t.append(np.broadcast_to(_unpack(xs_acc[j], xs_shapes[j]),
                                    _shape(v)).copy())
    return ([_unpack(c, s) for c, s in zip(consts_acc, const_shapes)] +
            [_unpack(c, s) for c, s in zip(carry_t, carry_shapes)] + xs_t)


def _rule_while(eqn, outs, bw, outer_env):
    p = eqn.params
    cond, body = p["cond_jaxpr"], p["body_jaxpr"]
    ncc, nbc = p["cond_nconsts"], p["body_nconsts"]
    carry_shapes = [np.asarray(t).shape for t in outs]
    carry_t = [_pack(t) for t in outs]
    const_shapes = [_shape(body.jaxpr.invars[i]) for i in range(nbc)]
    body_consts_acc = [BitMask.zeros(_size(s)) for s in const_shapes]
    benv = _sub_env(body.jaxpr, body.consts,
                    list(zip(body.jaxpr.invars[:nbc], eqn.invars[ncc:ncc + nbc])),
                    outer_env)
    cenv = _sub_env(cond.jaxpr, cond.consts,
                    list(zip(cond.jaxpr.invars[:ncc], eqn.invars[:ncc])),
                    outer_env)

    for it in range(_FIXPOINT_CAP):
        body_outs = [_unpack(c, s) for c, s in zip(carry_t, carry_shapes)]
        ins_t = bw(body.jaxpr, body.consts, body_outs, benv)
        for j in range(nbc):
            body_consts_acc[j].ior(_pack(ins_t[j]))
        new_carry = [c | _pack(t) for c, t in zip(carry_t, ins_t[nbc:])]
        if all(a == b for a, b in zip(new_carry, carry_t)):
            carry_t = new_carry
            break
        carry_t = new_carry
    else:
        carry_t = [BitMask.full(c.n) for c in carry_t]

    # The predicate gates every iteration → everything it reads is control
    # state (paper: loop indices are "obviously critical").
    any_out = any(t.any() for t in outs)
    cond_out = [np.full(_shape(cond.jaxpr.outvars[0]), any_out, bool)]
    cond_ins = bw(cond.jaxpr, cond.consts, cond_out, cenv)
    cond_consts_t = cond_ins[:ncc]
    carry_t = [_unpack(c | _pack(t), s)
               for c, t, s in zip(carry_t, cond_ins[ncc:], carry_shapes)]
    return (list(cond_consts_t) +
            [_unpack(c, s) for c, s in zip(body_consts_acc, const_shapes)] +
            carry_t)


def _rule_cond(eqn, outs, bw, outer_env):
    branches = eqn.params["branches"]
    ops = eqn.invars[1:]
    acc = [_zeros(v) for v in ops]
    for br in branches:
        benv = _sub_env(br.jaxpr, br.consts,
                        list(zip(br.jaxpr.invars, ops)), outer_env)
        ins_t = bw(br.jaxpr, br.consts, [np.asarray(t) for t in outs], benv)
        for j in range(len(ops)):
            acc[j] |= ins_t[j]
    any_out = any(t.any() for t in outs)
    return [_full(eqn.invars[0], any_out)] + acc


_FALLBACK_SEEN = set()


def _apply_rule(eqn, outs: List[np.ndarray], env, bw) -> List[Optional[np.ndarray]]:
    name = eqn.primitive.name

    if name in _ELEMENTWISE:
        t = np.zeros(outs[0].shape, bool)
        for o in outs:
            t |= o
        return [t if _shape(v) == t.shape else _full(v, t.any())
                for v in eqn.invars]

    if name in _VJP_STRUCTURAL:
        res = _vjp_structural(eqn, outs)
        if res is not None:
            return res

    if name in _REDUCE_AXES:
        axes = tuple(eqn.params["axes"])
        t = np.zeros(outs[0].shape, bool)
        for o in outs:
            t |= o
        in_shape = _shape(eqn.invars[0])
        t = np.expand_dims(t, axes) if t.ndim != len(in_shape) else t
        return [np.broadcast_to(t, in_shape).copy()]

    if name in _CUM_SUFFIX:
        return _rule_cum_suffix(eqn, outs)

    if name == "cumsum":
        return _rule_cum_suffix(eqn, outs)  # exact under 0/1 taint too

    if name == "dot_general":
        return _rule_dot_general(eqn, outs)

    if name == "fft":
        return _rule_fft(eqn, outs)

    if name == "gather":
        res = _rule_gather(eqn, outs, env)
        if res is not None:
            return res

    if name in ("scatter", "scatter-add", "scatter_add", "scatter-mul",
                "scatter_mul", "scatter-min", "scatter_min", "scatter-max",
                "scatter_max"):
        variant = "scatter" if name == "scatter" else "accum"
        res = _rule_scatter(eqn, outs, env, variant)
        if res is not None:
            return res

    if name == "dynamic_slice":
        res = _rule_dynamic_slice(eqn, outs, env)
        if res is not None:
            return res

    if name == "dynamic_update_slice":
        return _rule_dynamic_update_slice(eqn, outs, env)

    if name == "sort":
        return _rule_sort(eqn, outs)

    if name == "scan":
        return _rule_scan(eqn, outs, bw, env)

    if name == "while":
        return _rule_while(eqn, outs, bw, env)

    if name == "cond":
        return _rule_cond(eqn, outs, bw, env)

    if name in _RECURSE_CALLS:
        sub = _inner_closed(eqn)
        if sub is not None and len(sub.jaxpr.invars) == len(eqn.invars):
            return bw(sub.jaxpr, sub.consts, [np.asarray(t) for t in outs], env)

    if name == "top_k":
        t = np.zeros(_shape(eqn.outvars[0]), bool)
        for o in outs:
            t |= o
        in_shape = _shape(eqn.invars[0])
        tt = np.broadcast_to(t.any(axis=-1, keepdims=True), in_shape)
        return [tt.copy()]

    # Sound fallback: any tainted output ⇒ every input element tainted.
    if name not in _FALLBACK_SEEN:  # pragma: no cover - diagnostics only
        _FALLBACK_SEEN.add(name)
    any_out = any(t.any() for t in outs)
    return [_full(v, any_out) for v in eqn.invars]


# --------------------------------------------------------------------------
# Backward walker
# --------------------------------------------------------------------------

_NO_FOLD = {"scan", "while", "cond"} | _RECURSE_CALLS


def _fold_constants(jaxpr: jex_core.Jaxpr, env: Dict) -> Dict:
    """Best-effort forward folding of loop-invariant subexpressions.

    Inside scan/while/cond bodies only the consts are concrete; any index
    arithmetic derived purely from them (or from literals) can still be
    evaluated, which keeps gather/scatter windows exact inside loop bodies.
    """
    env = dict(env)
    for eqn in jaxpr.eqns:
        if eqn.primitive.name in _NO_FOLD:
            continue
        if all((not _is_drop(v)) and v in env for v in eqn.outvars):
            continue
        invals = []
        ok = True
        for v in eqn.invars:
            val = _concrete(v, env)
            if val is None:
                ok = False
                break
            invals.append(val)
        if not ok:
            continue
        try:
            outvals = eqn.primitive.bind(*invals, **eqn.params)
        except Exception:  # pragma: no cover - fold is best-effort
            continue
        if not eqn.primitive.multiple_results:
            outvals = [outvals]
        for v, val in zip(eqn.outvars, outvals):
            if not _is_drop(v):
                env[v] = val
    return env


def _backward(jaxpr: jex_core.Jaxpr, consts, out_taints: List[np.ndarray],
              env: Optional[Dict]) -> List[np.ndarray]:
    if env is not None:
        env = _fold_constants(jaxpr, env)
    # The lattice itself is bit-packed: one BitMask per var, OR-joined as
    # word ops.  Rules still see shaped bool arrays at the call boundary.
    taint: Dict[Any, BitMask] = {}

    def add(v, t):
        if isinstance(v, Literal) or t is None:
            return
        bm = _pack(np.broadcast_to(np.asarray(t, bool), _shape(v)))
        cur = taint.get(v)
        if cur is None:
            taint[v] = bm
        else:
            cur.ior(bm)

    for v, t in zip(jaxpr.outvars, out_taints):
        add(v, t)

    for eqn in reversed(jaxpr.eqns):
        raw = [None if _is_drop(v) else taint.get(v) for v in eqn.outvars]
        if not any(t is not None and t.any() for t in raw):
            continue
        outs = [_unpack(t, _shape(v)) if t is not None else _zeros(v)
                for t, v in zip(raw, eqn.outvars)]
        ins = _apply_rule(eqn, outs, env, _backward)
        for v, t in zip(eqn.invars, ins):
            add(v, t)

    return [_unpack(taint[v], _shape(v)) if v in taint else _zeros(v)
            for v in jaxpr.invars]


# --------------------------------------------------------------------------
# Value-dependence analysis: which leaves can change the masks?
# --------------------------------------------------------------------------

def _index_operand_positions(eqn) -> Tuple[int, ...]:
    """Operand positions whose concrete *values* the taint rules consult.

    The backward walk is structural everywhere except index resolution:
    ``_indexed_vjp`` reads the concrete values of gather/scatter indices
    and dynamic-slice starts (and falls back to a value-independent
    conservative rule when they are unknown).  These positions are the
    only places where a leaf's value — as opposed to its shape/dtype —
    can influence a mask: scan/while carries are never concrete inside
    bodies, and ``cond`` unions branches without consulting the predicate.
    """
    name = eqn.primitive.name
    if name == "gather":
        return (1,)
    if name.startswith("scatter"):
        return (1,)
    if name == "dynamic_slice":
        return tuple(range(1, len(eqn.invars)))
    if name == "dynamic_update_slice":
        return tuple(range(2, len(eqn.invars)))
    return ()


def _param_jaxprs(eqn):
    """Every sub-jaxpr reachable through ``eqn.params`` (one level)."""
    for val in eqn.params.values():
        items = val if isinstance(val, (list, tuple)) else (val,)
        for sub in items:
            if isinstance(sub, jex_core.ClosedJaxpr):
                yield sub.jaxpr
            elif isinstance(sub, jex_core.Jaxpr):
                yield sub


def _contains_dynamic_index(jaxpr, memo: Dict) -> bool:
    """Does ``jaxpr`` (transitively) index with a non-literal operand?"""
    key = ("dyn", id(jaxpr))
    if key in memo:
        return memo[key]
    memo[key] = False
    found = False
    for eqn in jaxpr.eqns:
        if any(not isinstance(eqn.invars[i], Literal)
               for i in _index_operand_positions(eqn)):
            found = True
            break
        if any(_contains_dynamic_index(sub, memo)
               for sub in _param_jaxprs(eqn)):
            found = True
            break
    memo[key] = found
    return found


def _needed_invars(jaxpr, memo: Dict) -> frozenset:
    """Invar positions of ``jaxpr`` whose concrete values can reach an
    index operand (at any nesting depth), assuming every var in this
    jaxpr may be concretely known — exact at the top level (full forward
    eval records every intermediate) and a sound over-approximation
    inside bodies.  Mapping into bodies mirrors ``_sub_env``: scan/while
    pass only their *const* operands concretely (carries and xs never
    are), ``cond`` passes every branch operand, calls map invars 1:1.
    """
    key = ("need", id(jaxpr))
    if key in memo:
        return memo[key]
    memo[key] = frozenset()          # jaxprs are acyclic; cheap guard
    feeding: set = set()             # vars whose value reaches an index
    changed = True
    while changed:
        changed = False
        for eqn in reversed(jaxpr.eqns):
            need = {i for i in _index_operand_positions(eqn)
                    if not isinstance(eqn.invars[i], Literal)}
            name = eqn.primitive.name
            if name == "scan":
                nc = eqn.params["num_consts"]
                need |= {i for i in _needed_invars(
                    eqn.params["jaxpr"].jaxpr, memo) if i < nc}
            elif name == "while":
                ncc = eqn.params["cond_nconsts"]
                nbc = eqn.params["body_nconsts"]
                need |= {i for i in _needed_invars(
                    eqn.params["cond_jaxpr"].jaxpr, memo) if i < ncc}
                need |= {ncc + i for i in _needed_invars(
                    eqn.params["body_jaxpr"].jaxpr, memo) if i < nbc}
            elif name == "cond":
                for br in eqn.params["branches"]:
                    need |= {1 + i for i in _needed_invars(br.jaxpr, memo)}
            elif name in _RECURSE_CALLS:
                sub = _inner_closed(eqn)
                if sub is not None and \
                        len(sub.jaxpr.invars) == len(eqn.invars):
                    need |= _needed_invars(sub.jaxpr, memo)
                elif any(_contains_dynamic_index(s, memo)
                         for s in _param_jaxprs(eqn)):
                    need.update(range(len(eqn.invars)))
            elif any(_contains_dynamic_index(s, memo)
                     for s in _param_jaxprs(eqn)):
                # unknown higher-order primitive wrapping a dynamic index:
                # assume every operand's value may reach it (sound)
                need.update(range(len(eqn.invars)))
            # transitive closure: anything feeding a value that later
            # reaches an index operand is itself value-consulted
            if any(not _is_drop(v) and v in feeding for v in eqn.outvars):
                need.update(range(len(eqn.invars)))
            for i in need:
                v = eqn.invars[i]
                if not isinstance(v, Literal) and v not in feeding:
                    feeding.add(v)
                    changed = True
    res = frozenset(i for i, v in enumerate(jaxpr.invars) if v in feeding)
    memo[key] = res
    return res


def index_feeding_invars(closed: jex_core.ClosedJaxpr) -> frozenset:
    """Top-level invar positions whose *values* can influence the masks.

    ``backward_taint`` consults concrete leaf values in exactly one
    place: resolving the index operands of gather/scatter/dynamic_slice/
    dynamic_update_slice (directly, or hoisted into control-flow bodies
    via the loop-invariant sub-env).  An invar outside the returned set
    cannot change any mask by changing value — the walk is purely
    structural in it.  The set is a value-independent, conservative
    over-approximation, so callers may key value-sensitive caches on a
    digest of exactly these leaves (``repro.core.criticality``'s
    static-prune cache does; re-using masks across different index values
    would silently zero-mask leaves that became live).
    """
    return _needed_invars(closed.jaxpr, {})


# --------------------------------------------------------------------------
# Public API
# --------------------------------------------------------------------------

def classify_rule(primitive_name: str) -> str:
    """Which taint rule class handles ``primitive_name``.

    Mirrors the :func:`_apply_rule` dispatch so provenance reports
    (``repro.analysis``) can attribute a mask decision to the responsible
    rule without re-running the walk.
    """
    name = primitive_name
    if name in _ELEMENTWISE:
        return "elementwise"
    if name in _VJP_STRUCTURAL or name == "cumsum":
        return "vjp_structural"
    if name in _REDUCE_AXES:
        return "reduce_axes"
    if name in _CUM_SUFFIX:
        return "cum_suffix"
    if name in ("dot_general", "fft", "sort", "top_k"):
        return name
    if name == "gather" or name == "dynamic_slice":
        return "indexed_read"
    if name.startswith("scatter") or name == "dynamic_update_slice":
        return "indexed_write"
    if name in ("scan", "while", "cond"):
        return "control_flow"
    if name in _RECURSE_CALLS:
        return "call"
    return "fallback"


def backward_taint(closed: jex_core.ClosedJaxpr,
                   leaves: Sequence[Any]) -> List[np.ndarray]:
    """Run the participation walk over an already-traced flat jaxpr.

    ``closed`` must be a flat leaves→leaves trace (e.g.
    ``repro.core.criticality.traced_step(fn, state).closed``); ``leaves``
    are the concrete invar values, used to resolve gather/scatter/
    dynamic-slice indices exactly.  Returns one shaped bool taint array per
    invar — True == read (transitively, before overwrite) by some output.
    Shared entry point for :func:`participation` and the static analyzer
    (``repro.analysis.analyze_static``).
    """
    env: Dict[Any, Any] = {}
    _forward_env(closed.jaxpr, closed.consts, list(leaves), env)
    out_taints = [np.ones(_shape(v), bool) for v in closed.jaxpr.outvars]
    return _backward(closed.jaxpr, closed.consts, out_taints, env)


def participation(
    fn: Callable[[Any], Any],
    state: Any,
    *,
    config: ScrutinyConfig = ScrutinyConfig(),
) -> CriticalityReport:
    """Element-granular read-participation analysis of ``fn`` at ``state``.

    Same contract and report type as :func:`repro.core.scrutinize`; the mask
    marks an element critical iff the remaining computation transitively
    reads it before overwriting it.  See module docstring for how this
    relates to the AD (vjp) engine.

    The walk's four phases are spans (``scrutiny.trace``, ``.forward``,
    ``.taint``, ``.tables``), and their seconds are always in the report's
    ``stats["stages"]`` (``trace_s``, ``forward_s``, ``taint_s``,
    ``tables_s``).
    """
    tracer = obs_mod.get_obs().tracer
    stages: Dict[str, float] = {}

    @contextlib.contextmanager
    def stage(name: str):
        t0 = time.perf_counter()
        with tracer.span("scrutiny." + name):
            yield
        stages[name + "_s"] = time.perf_counter() - t0

    with stage("trace"):
        ts = traced_step(fn, state)
    names, leaves, closed = ts.names, ts.leaves, ts.closed
    env: Dict[Any, Any] = {}
    with stage("forward"):
        _forward_env(closed.jaxpr, closed.consts, list(leaves), env)
    with stage("taint"):
        out_taints = [np.ones(_shape(v), bool) for v in closed.jaxpr.outvars]
        in_taints = _backward(closed.jaxpr, closed.consts, out_taints, env)
    del env

    reports: Dict[str, LeafReport] = {}
    with stage("tables"):
        policies = [config.leaf_policy(l) for l in leaves]
        for i, (name, leaf, pol) in enumerate(zip(names, leaves, policies)):
            n = int(np.prod(leaf.shape)) if leaf.ndim else 1
            if pol in (LeafPolicy.AD, LeafPolicy.HORIZON):
                mask = in_taints[i].reshape(-1).copy()
                if mask.size == 0 and n == 1:
                    mask = np.zeros(1, bool)
            elif pol == LeafPolicy.ALWAYS_CRITICAL:
                mask = np.ones(n, dtype=bool)
            else:
                mask = np.zeros(n, dtype=bool)
            if mask.size != n:  # 0-d leaves
                mask = np.resize(mask, n)
            table = RegionTable.from_mask(
                mask, itemsize=np.dtype(leaf.dtype).itemsize)
            table.validate()
            reports[name] = LeafReport(
                name=name, shape=tuple(leaf.shape), dtype=np.dtype(leaf.dtype),
                policy=pol, mask=mask, table=table, magnitude=None,
            )
    return CriticalityReport(leaves=reports, stats={"stages": stages})
