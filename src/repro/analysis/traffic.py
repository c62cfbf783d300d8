"""Static traffic audit: walk a kernel's jaxpr and count its streams.

The paper's model consumes two code features per kernel — the stream
decomposition (reads / writes / write-allocate RFOs) and the flops per
lattice update — which Table II transcribes by hand.  Kerncraft
(arXiv:1509.03778) showed these features fall out of static analysis of
the loop body; this module is that analysis for the repo's own
jax/pallas kernels, operating on the *closed jaxpr* instead of C source:

* every ``pallas_call`` is decomposed through its ``grid_mapping`` —
  each :class:`BlockMapping`'s index map is analyzed for which grid axes
  it depends on (backward reachability over the index-map jaxpr), which
  yields how often the block is (re)fetched across the sequential grid
  walk and therefore the stream's total element traffic;
* ``scan`` / ``while`` / ``cond`` / ``jit`` (and the other call-like
  primitives) are recursed into, multiplying trip counts where they are
  static and recording a note where they are not;
* flops are counted per arithmetic primitive (elementwise ops charge
  their output element count, reductions their input count,
  ``dot_general`` the usual ``2·M·N·K``), and ``gather``/``scatter``
  primitives are classified separately from streaming accesses;
* base-buffer provenance is tracked through view primitives (``slice``,
  ``reshape``, ``transpose``, …), so three shifted views of one array —
  the Jacobi up/mid/down rows — are recognized as streams over a single
  base buffer.  :mod:`repro.analysis.features` uses exactly that to
  apply (or refuse) the paper's layer condition.

The result is a :class:`TrafficAudit`: one :class:`Stream` per moved
block plus flop and iteration totals, normalized downstream by
:func:`repro.analysis.features.derive` into the ``LoopFeatures`` that
feed the registry's ECM bridge.
"""

from __future__ import annotations

import dataclasses
import inspect
import math
from typing import Any, Callable, Sequence

from ..core.backend import HAVE_JAX

if HAVE_JAX:
    import jax
    from jax import core as jax_core  # noqa: F401  (Var/Literal live here)

#: Primitives that merely re-view their (first) operand: base-buffer
#: provenance flows through them unchanged.
_VIEW_PRIMS = frozenset({
    "slice", "dynamic_slice", "reshape", "squeeze", "expand_dims",
    "transpose", "rev", "broadcast_in_dim", "convert_element_type",
    "copy", "bitcast_convert_type", "stop_gradient",
})

#: Call-like primitives recursed into with an unchanged trip multiplier.
_CALL_PRIMS = frozenset({
    "jit", "closed_call", "core_call", "custom_jvp_call",
    "custom_vjp_call", "remat", "checkpoint", "custom_vjp_call_jaxpr",
})

#: flops charged per *output element* for elementwise arithmetic.  Ops
#: that move/select/compare data (select_n, iota, concatenate, pad,
#: comparisons, boolean logic) are deliberately absent: they cost no
#: floating-point work in the paper's accounting.
_ELEMENTWISE_FLOPS = {
    "add": 1, "sub": 1, "mul": 1, "div": 1, "rem": 1, "neg": 1,
    "max": 1, "min": 1, "abs": 1, "sign": 1,
    "exp": 1, "exp2": 1, "log": 1, "log1p": 1, "expm1": 1,
    "sqrt": 1, "rsqrt": 1, "cbrt": 1, "pow": 1, "integer_pow": 1,
    "sin": 1, "cos": 1, "tan": 1, "tanh": 1, "logistic": 1, "erf": 1,
    "atan2": 1, "square": 1, "reciprocal": 1,
    "add_any": 1, "fma": 2,
}

#: Reductions charge their *input* element count (one op per consumed
#: element, the paper's convention for ``s += a[i]``-style loops).
_REDUCE_FLOPS = {
    "reduce_sum": 1, "reduce_prod": 1, "reduce_max": 0, "reduce_min": 0,
    "cumsum": 1, "cumprod": 1, "cumlogsumexp": 2,
}

_GATHER_PRIMS = frozenset({"gather", "dynamic_gather"})
_SCATTER_PRIMS = frozenset({
    "scatter", "scatter-add", "scatter_add", "scatter_mul", "scatter_min",
    "scatter_max",
})


@dataclasses.dataclass(frozen=True)
class Stream:
    """One moved data stream of a kernel call.

    ``elements`` is the total element traffic over the whole call (all
    grid invocations × the block size), *not* per iteration — the
    per-iteration normalization happens in
    :func:`repro.analysis.features.derive`.
    """

    base: str           # source buffer label ("a", "arrays[1]", "<out0>")
    kind: str           # "load" | "store" | "resident" | "accumulator"
    elements: int
    itemsize: int
    fetches: int        # grid invocations that (re)fetch the block
    block_shape: tuple[int, ...]
    aliased: bool = False   # store aliased onto an input (in-place write)
    indexed: str = "affine"  # "affine" | "gather" | "scatter"

    @property
    def bytes(self) -> int:
        return self.elements * self.itemsize


@dataclasses.dataclass(frozen=True)
class TrafficAudit:
    """The walker's verdict on one traced kernel call."""

    name: str
    streams: tuple[Stream, ...]
    flops: float        # total floating-point ops per call
    iters: int          # lattice updates per call (store-stream normalized)
    reductions: int     # cross-grid accumulator outputs
    gathers: int        # gather-indexed accesses seen
    scatters: int
    notes: tuple[str, ...]

    def by_kind(self, kind: str) -> tuple[Stream, ...]:
        return tuple(s for s in self.streams if s.kind == kind)

    @property
    def loads(self) -> tuple[Stream, ...]:
        return self.by_kind("load")

    @property
    def stores(self) -> tuple[Stream, ...]:
        return self.by_kind("store")

    @property
    def total_bytes(self) -> int:
        return sum(s.bytes for s in self.streams
                   if s.kind in ("load", "store"))

    @property
    def flops_per_iter(self) -> float:
        return self.flops / self.iters if self.iters else 0.0


class _State:
    """Mutable accumulator threaded through the walk."""

    def __init__(self) -> None:
        self.streams: list[Stream] = []
        self.flops: float = 0.0
        self.reductions: int = 0
        self.gathers: int = 0
        self.scatters: int = 0
        self.notes: list[str] = []

    def merge(self, other: "_State") -> None:
        self.streams.extend(other.streams)
        self.flops += other.flops
        self.reductions += other.reductions
        self.gathers += other.gathers
        self.scatters += other.scatters
        self.notes.extend(other.notes)

    @property
    def moved_bytes(self) -> int:
        return sum(s.bytes for s in self.streams
                   if s.kind in ("load", "store"))


# ---------------------------------------------------------------------------
# Argument labeling: jaxpr invars -> human-readable base-buffer names
# ---------------------------------------------------------------------------


def _arg_labels(fn: Callable, args: Sequence[Any]) -> list[str]:
    """One label per *flattened* leaf of ``args``, in the order
    ``jax.make_jaxpr`` flattens them, derived from ``fn``'s signature
    (``functools.partial`` is handled by ``inspect``)."""
    names: list[str] = []
    try:
        bound = inspect.signature(fn).bind(*args)
        items = list(bound.arguments.items())
    except (TypeError, ValueError):
        items = [(f"args[{i}]", a) for i, a in enumerate(args)]
    for pname, value in items:
        if isinstance(value, tuple) and not hasattr(value, "shape"):
            sub = [(f"{pname}[{i}]", v) for i, v in enumerate(value)]
        else:
            sub = [(pname, value)]
        for label, v in sub:
            leaves = jax.tree_util.tree_leaves(v)
            if len(leaves) <= 1:
                names.append(label)
            else:
                names.extend(f"{label}.{j}" for j in range(len(leaves)))
    return names


def _base_of(env: dict, atom) -> str:
    """Base label of a jaxpr atom: tracked for vars, synthetic for
    literals/consts."""
    if hasattr(atom, "val"):  # Literal
        return "<lit>"
    return env.get(atom, "<tmp>")


# ---------------------------------------------------------------------------
# Flop counting (shared by the outer walk and pallas kernel bodies)
# ---------------------------------------------------------------------------


def _aval_size(aval) -> int:
    return int(math.prod(getattr(aval, "shape", ()) or (1,)))


def _sub_jaxprs(params: dict):
    """Every (multiplier, jaxpr) pair reachable from an eqn's params."""
    for key in ("jaxpr", "call_jaxpr", "cond_jaxpr", "body_jaxpr"):
        sub = params.get(key)
        if sub is not None:
            yield 1.0, getattr(sub, "jaxpr", sub)
    for branch in params.get("branches", ()) or ():
        yield 1.0, getattr(branch, "jaxpr", branch)


def _count_flops(jaxpr, mult: float, st: _State) -> float:
    """Total flops of one (sub-)jaxpr, recursing into call-like and
    control-flow primitives; also tallies gather/scatter sightings."""
    total = 0.0
    for eqn in jaxpr.eqns:
        prim = eqn.primitive.name
        if prim in _ELEMENTWISE_FLOPS:
            out_elems = sum(_aval_size(v.aval) for v in eqn.outvars)
            total += _ELEMENTWISE_FLOPS[prim] * out_elems * mult
        elif prim in _REDUCE_FLOPS:
            in_elems = _aval_size(eqn.invars[0].aval)
            total += _REDUCE_FLOPS[prim] * in_elems * mult
        elif prim == "dot_general":
            dims = eqn.params["dimension_numbers"]
            (lhs_c, _), _ = dims
            lhs = eqn.invars[0].aval
            k = math.prod(lhs.shape[i] for i in lhs_c) or 1
            out_elems = _aval_size(eqn.outvars[0].aval)
            total += 2.0 * out_elems * k * mult
        elif prim in _GATHER_PRIMS:
            st.gathers += 1
        elif prim in _SCATTER_PRIMS:
            st.scatters += 1
        elif prim == "scan":
            length = float(eqn.params.get("length", 1))
            inner = eqn.params["jaxpr"]
            total += _count_flops(getattr(inner, "jaxpr", inner),
                                  mult * length, st)
        elif prim == "while":
            body = eqn.params["body_jaxpr"]
            total += _count_flops(getattr(body, "jaxpr", body), mult, st)
        elif prim == "cond":
            per_branch = [
                _count_flops(getattr(b, "jaxpr", b), mult, _State())
                for b in eqn.params["branches"]]
            total += max(per_branch, default=0.0)
        else:
            for sub_mult, sub in _sub_jaxprs(eqn.params):
                total += _count_flops(sub, mult * sub_mult, st)
    return total


# ---------------------------------------------------------------------------
# pallas_call decomposition
# ---------------------------------------------------------------------------


def _index_map_deps(index_map_jaxpr, n_axes: int) -> list[int]:
    """Grid axes the block's index map actually reads: backward
    reachability from the index-map outvars to its (grid-index)
    invars."""
    jaxpr = getattr(index_map_jaxpr, "jaxpr", index_map_jaxpr)
    needed = {v for v in jaxpr.outvars if not hasattr(v, "val")}
    changed = True
    while changed:
        changed = False
        for eqn in jaxpr.eqns:
            if any(ov in needed for ov in eqn.outvars):
                for iv in eqn.invars:
                    if not hasattr(iv, "val") and iv not in needed:
                        needed.add(iv)
                        changed = True
    return [i for i, v in enumerate(jaxpr.invars[:n_axes]) if v in needed]


def _block_elems(block_shape) -> int:
    return int(math.prod(max(d, 1) for d in block_shape))


def _fetches(deps: Sequence[int], grid: Sequence[int]) -> int:
    """(Re)fetch count of a block over the sequential grid walk: a block
    depending on axes ``deps`` is refetched once per combination of the
    axes up to (and including) its slowest-varying dependence — inner
    independent axes revisit the resident block for free."""
    if not deps:
        return 1
    return int(math.prod(grid[:max(deps) + 1])) or 1


def _audit_pallas(eqn, env: dict, mult: float, st: _State) -> None:
    params = eqn.params
    gm = params["grid_mapping"]
    grid = tuple(int(g) for g in gm.grid) or (1,)
    n_axes = len(grid)
    bms = list(gm.block_mappings)
    n_out = int(gm.num_outputs)
    in_bms, out_bms = bms[:len(bms) - n_out], bms[len(bms) - n_out:]

    # Align block-mapped operands with the eqn's invars: scalar-prefetch
    # (index) operands precede them and carry no block mapping.
    invars = list(eqn.invars)
    offset = len(invars) - len(in_bms)
    if offset < 0:  # defensive: never index past the operand list
        offset = 0
    op_invars = invars[offset:]
    for j in range(offset):
        st.notes.append(
            f"pallas scalar-prefetch operand "
            f"{_base_of(env, invars[j])!r} held resident (not a stream)")

    aliases = {}
    for pair in (params.get("input_output_aliases") or ()):
        try:
            i_in, i_out = int(pair[0]), int(pair[1])
        except (TypeError, ValueError, IndexError):
            continue
        aliases[i_out] = i_in

    def _stream(bm, aval, base, is_output, out_idx=None):
        deps = _index_map_deps(bm.index_map_jaxpr, n_axes)
        fetches = _fetches(deps, grid)
        # Blocked / Element / BoundedSlice dims carry their size;
        # a Squeezed dim moves one element.
        block_shape = tuple(getattr(d, "block_size", 1)
                            for d in bm.block_shape)
        elements = _block_elems(block_shape) * fetches
        itemsize = int(getattr(getattr(aval, "dtype", None), "itemsize", 4))
        if is_output:
            if not deps:
                st.reductions += 1
                kind = "accumulator"
            else:
                kind = "store"
        else:
            kind = "load" if deps else "resident"
        aliased = False
        if is_output and out_idx is not None and out_idx in aliases:
            a_in = aliases[out_idx] - (len(invars) - len(op_invars))
            if 0 <= a_in < len(op_invars):
                base = _base_of(env, op_invars[a_in])
                aliased = True
        st.streams.append(Stream(
            base=base, kind=kind, elements=int(elements * mult),
            itemsize=itemsize, fetches=int(fetches * mult),
            block_shape=block_shape, aliased=aliased))

    for j, (iv, bm) in enumerate(zip(op_invars, in_bms)):
        _stream(bm, iv.aval, _base_of(env, iv), is_output=False)
    for j, bm in enumerate(out_bms):
        aval = eqn.outvars[j].aval if j < len(eqn.outvars) else None
        _stream(bm, aval, f"<out{j}>", is_output=True, out_idx=j)

    kernel_jaxpr = params.get("jaxpr")
    if kernel_jaxpr is not None:
        invocations = math.prod(grid)
        st.flops += _count_flops(getattr(kernel_jaxpr, "jaxpr",
                                         kernel_jaxpr),
                                 mult * invocations, st)


# ---------------------------------------------------------------------------
# The outer walk
# ---------------------------------------------------------------------------


def _walk(jaxpr, env: dict, mult: float, st: _State) -> None:
    for eqn in jaxpr.eqns:
        prim = eqn.primitive.name
        if prim == "pallas_call":
            _audit_pallas(eqn, env, mult, st)
        elif prim == "scan":
            length = float(eqn.params.get("length", 1))
            inner = eqn.params["jaxpr"]
            sub = getattr(inner, "jaxpr", inner)
            sub_env = {iv: _base_of(env, ov)
                       for iv, ov in zip(sub.invars, eqn.invars)}
            _walk(sub, sub_env, mult * length, st)
        elif prim == "while":
            body = eqn.params["body_jaxpr"]
            sub = getattr(body, "jaxpr", body)
            # invars = cond consts + body consts + carry; the body jaxpr
            # sees body consts + carry.
            cc = int(eqn.params.get("cond_nconsts", 0))
            sub_env = {iv: _base_of(env, ov)
                       for iv, ov in zip(sub.invars, eqn.invars[cc:])}
            st.notes.append(
                "while_loop trip count is data-dependent: its body is "
                "counted once (scale the audit by the expected trips)")
            _walk(sub, sub_env, mult, st)
        elif prim == "cond":
            branch_states = []
            for branch in eqn.params["branches"]:
                sub = getattr(branch, "jaxpr", branch)
                sub_env = {iv: _base_of(env, ov)
                           for iv, ov in zip(sub.invars, eqn.invars[1:])}
                bst = _State()
                _walk(sub, sub_env, mult, bst)
                branch_states.append(bst)
            if branch_states:
                worst = max(branch_states, key=lambda b: b.moved_bytes)
                if len(branch_states) > 1:
                    st.notes.append(
                        "cond: counted the heaviest branch "
                        f"({worst.moved_bytes} B of "
                        f"{sorted(b.moved_bytes for b in branch_states)})")
                st.merge(worst)
        elif prim in _CALL_PRIMS:
            for _, sub in _sub_jaxprs(eqn.params):
                sub_env = {iv: _base_of(env, ov)
                           for iv, ov in zip(sub.invars, eqn.invars)}
                _walk(sub, sub_env, mult, st)
                for ov, sv in zip(eqn.outvars, sub.outvars):
                    env[ov] = _base_of(sub_env, sv)
                break
        else:
            if prim in _VIEW_PRIMS and eqn.invars:
                for ov in eqn.outvars:
                    env[ov] = _base_of(env, eqn.invars[0])
            if prim in _ELEMENTWISE_FLOPS or prim in _REDUCE_FLOPS \
                    or prim == "dot_general" or prim in _GATHER_PRIMS \
                    or prim in _SCATTER_PRIMS:
                shim = type("_J", (), {"eqns": [eqn]})()
                st.flops += _count_flops(shim, mult, st)


def _fallback_streams(closed, labels: list[str], st: _State) -> None:
    """No pallas_call anywhere: charge whole-array traffic at the
    jaxpr boundary (consumed invars load, outvars store) so plain-jnp
    functions still audit to something meaningful."""
    jaxpr = closed.jaxpr
    used = set()
    stack = list(jaxpr.eqns)
    while stack:
        eqn = stack.pop()
        used.update(v for v in eqn.invars if not hasattr(v, "val"))
        for _, sub in _sub_jaxprs(eqn.params):
            stack.extend(sub.eqns)
    out_vars = {v for v in jaxpr.outvars if not hasattr(v, "val")}
    for i, iv in enumerate(jaxpr.invars):
        if iv not in used or not getattr(iv.aval, "shape", ()):
            continue
        st.streams.append(Stream(
            base=labels[i] if i < len(labels) else f"args[{i}]",
            kind="load", elements=_aval_size(iv.aval),
            itemsize=int(iv.aval.dtype.itemsize), fetches=1,
            block_shape=tuple(iv.aval.shape)))
    for j, ov in enumerate(jaxpr.outvars):
        if hasattr(ov, "val") or not getattr(ov.aval, "shape", ()):
            continue
        st.streams.append(Stream(
            base=f"<out{j}>", kind="store",
            elements=_aval_size(ov.aval),
            itemsize=int(ov.aval.dtype.itemsize), fetches=1,
            block_shape=tuple(ov.aval.shape),
            aliased=ov in {v for v in jaxpr.invars}))
    st.notes.append("no pallas_call found: streams charged at the "
                    "jaxpr boundary (whole-array traffic)")


def _normalize_iters(streams: Sequence[Stream]) -> int:
    """Lattice updates per call: the largest store stream's element
    count (every Table II kernel writes each site once), falling back
    to the largest load stream for read-only reductions."""
    stores = [s.elements for s in streams if s.kind == "store"]
    if stores:
        return max(stores)
    loads = [s.elements for s in streams if s.kind == "load"]
    return max(loads) if loads else 1


def audit(fn: Callable, *args: Any, name: str | None = None
          ) -> TrafficAudit:
    """Trace ``fn(*args)`` and statically account its memory traffic.

    ``fn`` must be traceable by :func:`jax.make_jaxpr` with the given
    concrete (or shape-struct) arguments; nothing is executed.  Use
    ``functools.partial`` to bind non-traceable arguments (kernel-name
    strings, static configuration).
    """
    if not HAVE_JAX:
        raise RuntimeError(
            "static analysis requires jax (jax.make_jaxpr); it is not "
            "importable in this environment")
    closed = jax.make_jaxpr(fn)(*args)
    labels = _arg_labels(fn, args)
    jaxpr = closed.jaxpr
    env: dict = {}
    for i, iv in enumerate(jaxpr.invars):
        env[iv] = labels[i] if i < len(labels) else f"args[{i}]"
    for cv in jaxpr.constvars:
        env[cv] = "<const>"
    st = _State()
    _walk(jaxpr, env, 1.0, st)
    if not any(s.kind in ("load", "store") for s in st.streams):
        _fallback_streams(closed, labels, st)
    iters = _normalize_iters(st.streams)
    if name is None:
        name = getattr(fn, "__name__", None) or \
            getattr(getattr(fn, "func", None), "__name__", "kernel")
    return TrafficAudit(
        name=name, streams=tuple(st.streams), flops=st.flops,
        iters=iters, reductions=st.reductions, gathers=st.gathers,
        scatters=st.scatters, notes=tuple(dict.fromkeys(st.notes)))
