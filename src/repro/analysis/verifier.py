"""Jaxpr-level static safety checker — the eBPF verifier analogue
(DESIGN.md §12).

The paper's datapath is trusted in-kernel only because the eBPF verifier
statically proves every map access in bounds before the program may load.
This module plays that role for the reproduction: each registered Pallas
kernel is traced via ``jax.make_jaxpr`` (never executed) and the jaxpr is
walked with an interval abstract domain — every variable carries a sound
``[lo, hi]`` over its possible values — to prove:

  * every ``gather`` / ``scatter`` whose mode is ``PROMISE_IN_BOUNDS`` (the
    form plain ``x[i]`` lowers to) has index operands whose interval fits
    the indexed window — i.e. the index derives from a ``clip`` / ``%`` /
    ``iota`` / ``argmax``-style bounded source, not a raw table read;
  * every dynamic index into a Pallas ``Ref`` (``get``/``swap``/
    ``addupdate`` NDIndexers) is likewise proven, since compiled Mosaic
    refs have **no** OOB clamping at all;
  * no primitive produces a 64-bit value (float64/int64 promotion breaks
    the int32 table contract and the TPU lowering) and no nondeterministic
    RNG primitive appears in a datapath trace.

Entry assumptions come from :data:`repro.analysis.invariants.FIELD_BOUNDS`:
the verifier *assumes* exactly the table-value bounds the plan validator
*enforces* on every wire payload (``core/control.py::unpack_plan``) —
mirroring the split between the eBPF verifier and the map-update
sanitization in the paper.  Neither side is sound alone.

Scatters with ``FILL_OR_DROP``/``CLIP`` modes (``.at[].set(mode="drop")``
and friends) are safe by construction and need no proof; the companion AST
lint (:mod:`repro.analysis.lint`) separately enforces that computed
scatters *spell* an explicit OOB mode.

``verify_kernels()`` sweeps admit / admit_commit / complete /
route_match / the sharded admit relay under both folds on representative
shapes from ``kernels/tune.py``; because the admit kernel folds every
``PolicyDef.kernel_offset`` through one ``jnp.select`` (and the staged
chain every ``staged_offset``), a newly registered policy is swept
automatically with no verifier change.
"""

from __future__ import annotations

import dataclasses
import math

import numpy as np

from repro.analysis.invariants import FIELD_BOUNDS

NEG = float("-inf")
POS = float("inf")

#: Primitives that WRITE through a Pallas ref — a ref touched by one of
#: these anywhere in a kernel gets TOP at entry (its content is no longer
#: the operand the wrapper passed in).
WRITE_PRIMS = ("swap", "addupdate", "masked_swap")

#: Nondeterministic / stateful RNG primitives.  Seeded ``jax.random``
#: (threefry bit math) is deterministic and allowed; these are not.
RNG_PRIMS = ("rng_uniform", "rng_bit_generator")

_64BIT = ("float64", "int64", "uint64", "complex128")


# --------------------------------------------------------------------------- #
# The interval domain.
# --------------------------------------------------------------------------- #


@dataclasses.dataclass(frozen=True)
class Interval:
    """Sound value bounds; ``None`` = unbounded on that side."""

    lo: float | None = None
    hi: float | None = None

    def __repr__(self):
        f = lambda v, s: s if v is None else f"{v:g}"
        return f"[{f(self.lo, '-inf')}, {f(self.hi, 'inf')}]"


TOP = Interval()


def _lo(iv):
    return NEG if iv.lo is None else iv.lo


def _hi(iv):
    return POS if iv.hi is None else iv.hi


def _mk(lo, hi):
    return Interval(None if lo == NEG else lo, None if hi == POS else hi)


def _hull(*ivs):
    ivs = [i for i in ivs if i is not None]
    if not ivs:
        return TOP
    return _mk(min(_lo(i) for i in ivs), max(_hi(i) for i in ivs))


def _meet(a, b):
    lo, hi = max(_lo(a), _lo(b)), min(_hi(a), _hi(b))
    return _mk(lo, hi) if lo <= hi else _mk(lo, lo)


def _shift(iv, k):
    return _mk(_lo(iv) + k, _hi(iv) + k)


def _pmul(x, y):
    """inf-safe product for bound candidates (0 * inf = 0)."""
    if x == 0 or y == 0:
        return 0
    return x * y


@dataclasses.dataclass(frozen=True)
class Finding:
    """One verifier diagnostic.  ``code`` is the stable machine-matchable
    name (what the mutation tests assert on); ``where`` locates the trace
    (kernel × fold × primitive)."""

    code: str
    where: str
    detail: str

    def __str__(self):
        return f"[{self.code}] {self.where}: {self.detail}"


def _is_literal(atom):
    return hasattr(atom, "val")


def _const_interval(val):
    a = np.asarray(val)
    if a.size == 0 or not (np.issubdtype(a.dtype, np.integer)
                           or np.issubdtype(a.dtype, np.floating)
                           or a.dtype == np.bool_):
        return TOP
    lo, hi = a.min(), a.max()
    if not (np.isfinite(lo) and np.isfinite(hi)):
        return TOP
    if np.issubdtype(a.dtype, np.integer) or a.dtype == np.bool_:
        return Interval(int(lo), int(hi))
    return Interval(float(lo), float(hi))


def _aval_dtype(aval):
    # AbstractMemoryRef wraps the array aval; plain ShapedArray has .dtype
    inner = getattr(aval, "inner_aval", aval)
    return getattr(inner, "dtype", None)


def _aval_shape(aval):
    inner = getattr(aval, "inner_aval", aval)
    return tuple(getattr(inner, "shape", ()))


def _dtype_default(aval):
    """The widest interval a value of this dtype can hold — the fallback
    for unhandled primitives (never ``TOP`` for bools/unsigned, which is
    what makes mask-hash chains like ``flow_hash`` provable)."""
    dt = _aval_dtype(aval)
    if dt is None:
        return TOP
    try:
        dt = np.dtype(dt)
    except TypeError:                 # extended dtypes (PRNG keys, …)
        return TOP
    if dt == np.bool_:
        return Interval(0, 1)
    if dt.kind == "u":
        return Interval(0, int(2 ** (8 * dt.itemsize)) - 1)
    if dt.kind == "i":
        n = 8 * dt.itemsize
        return Interval(-int(2 ** (n - 1)), int(2 ** (n - 1)) - 1)
    return TOP


def _sub_jaxprs(obj):
    """Yield every Jaxpr found in a params value (handles ClosedJaxpr,
    bare Jaxpr, and tuples/lists of either)."""
    vals = obj if isinstance(obj, (tuple, list)) else [obj]
    for v in vals:
        if hasattr(v, "jaxpr") and hasattr(v, "consts"):     # ClosedJaxpr
            yield v.jaxpr, list(v.consts)
        elif hasattr(v, "eqns") and hasattr(v, "invars"):    # Jaxpr
            yield v, None


def _invar_maps(eqn):
    """(sub_jaxpr, consts, {sub_invar_pos: outer_atom_pos}) for each
    sub-jaxpr of an eqn — the best-effort alignment the written-ref
    analysis and the recursive walk both use."""
    name = eqn.primitive.name
    out = []
    if name == "cond":
        for sub, consts in _sub_jaxprs(eqn.params.get("branches", ())):
            out.append((sub, consts,
                        {i: i + 1 for i in range(len(sub.invars))}))
    elif name == "while":
        cn = eqn.params.get("cond_nconsts", 0)
        body, bconsts = next(_sub_jaxprs(eqn.params["body_jaxpr"]))
        out.append((body, bconsts,
                    {i: cn + i for i in range(len(body.invars))}))
        cond, cconsts = next(_sub_jaxprs(eqn.params["cond_jaxpr"]))
        out.append((cond, cconsts, {i: i for i in range(cn)}))
    elif name == "pallas_call":
        gm = eqn.params.get("grid_mapping")
        ni = getattr(gm, "num_index_operands", 0)
        n_in = getattr(gm, "num_inputs", 0)
        sub, consts = next(_sub_jaxprs(eqn.params["jaxpr"]))
        mapping = {j: j for j in range(min(ni + n_in, len(eqn.invars)))}
        out.append((sub, consts, mapping))
    else:
        for key in ("jaxpr", "call_jaxpr"):
            if key in eqn.params:
                for sub, consts in _sub_jaxprs(eqn.params[key]):
                    n = min(len(sub.invars), len(eqn.invars))
                    out.append((sub, consts, {i: i for i in range(n)}))
                break
    return out


def _written_positions(jaxpr, memo):
    """Invar positions of ``jaxpr`` whose refs are written (directly or via
    a sub-jaxpr).  Sound: unmapped sub-jaxpr ref invars taint every outer
    ref operand of the eqn."""
    key = id(jaxpr)
    if key in memo:
        return memo[key]
    memo[key] = set()               # cycles cannot occur; terminate anyway
    written = set()                 # Vars of this jaxpr that are written
    for eqn in jaxpr.eqns:
        if eqn.primitive.name in WRITE_PRIMS:
            if not _is_literal(eqn.invars[0]):
                written.add(eqn.invars[0])
            continue
        subs = _invar_maps(eqn)
        if not subs and any(_sub_jaxprs(v) and False for v in ()):
            pass
        for sub, _consts, mapping in subs:
            for pos in _written_positions(sub, memo):
                outer_pos = mapping.get(pos)
                if outer_pos is not None and outer_pos < len(eqn.invars):
                    atom = eqn.invars[outer_pos]
                    if not _is_literal(atom):
                        written.add(atom)
    pos = {i for i, v in enumerate(jaxpr.invars) if v in written}
    memo[key] = pos
    return pos


# --------------------------------------------------------------------------- #
# The analyzer.
# --------------------------------------------------------------------------- #

_PASSTHROUGH = {
    "broadcast_in_dim", "reshape", "squeeze", "expand_dims", "transpose",
    "rev", "slice", "copy", "convert_element_type", "stop_gradient",
    "reduce_max", "reduce_min", "cummax", "cummin", "reduce_or",
    "reduce_and", "all_gather", "all_to_all", "ppermute", "pbroadcast",
    "reduce_precision", "sharding_constraint", "device_put", "real",
}

_BOOL_OUT = {
    "lt", "le", "gt", "ge", "eq", "ne", "le_to", "lt_to", "not", "is_finite",
    "reduce_xor",
}


class _Analyzer:
    def __init__(self, findings: list, where: str):
        self.findings = findings
        self.where = where
        self._written_memo: dict[int, set] = {}

    # ---- bookkeeping ---------------------------------------------------- #

    def flag(self, code, detail, prim=""):
        where = f"{self.where}/{prim}" if prim else self.where
        self.findings.append(Finding(code, where, detail))

    def read(self, env, atom):
        if _is_literal(atom):
            return _const_interval(atom.val)
        return env.get(atom, _dtype_default(atom.aval))

    def set(self, env, var, iv):
        env[var] = _meet(iv, _dtype_default(var.aval))

    def parts(self, env, atom):
        """Per-piece intervals if ``atom`` is (a shape-op away from) a
        ``concatenate`` — lets a stacked (i, j) index pair check each dim
        against its own bound."""
        seen = 0
        while not _is_literal(atom) and seen < 8:
            eqn = self._defs.get(atom)
            if eqn is None:
                return None
            name = eqn.primitive.name
            if name == "concatenate":
                return [self.read(env, v) for v in eqn.invars]
            if name in ("broadcast_in_dim", "reshape", "copy",
                        "convert_element_type"):
                atom = eqn.invars[0]
                seen += 1
                continue
            return None
        return None

    # ---- dtype / determinism sweeps ------------------------------------- #

    def _check_eqn_hygiene(self, eqn):
        name = eqn.primitive.name
        if name in RNG_PRIMS:
            self.flag("rng-in-datapath",
                      f"nondeterministic RNG primitive {name!r} in a "
                      "datapath trace (draw host randomness outside and "
                      "pass it in)", name)
        for v in eqn.outvars:
            dt = _aval_dtype(v.aval)
            try:
                dt = np.dtype(dt) if dt is not None else None
            except TypeError:         # extended dtypes (PRNG keys, …)
                dt = None
            if dt is not None and str(dt) in _64BIT:
                self.flag("x64-promotion",
                          f"primitive {name!r} produces {dt} "
                          "(64-bit values break the int32 table contract "
                          "and the Mosaic lowering)", name)

    # ---- gather / scatter proofs ---------------------------------------- #

    def _gather_allowed(self, eqn):
        """Per-mapped-dim max start index: shape[d] - slice_sizes[d]."""
        dnums = eqn.params["dimension_numbers"]
        op_shape = _aval_shape(eqn.invars[0].aval)
        ss = eqn.params.get("slice_sizes")
        out = []
        for d in dnums.start_index_map:
            size = ss[d] if ss is not None else 1
            out.append(op_shape[d] - size)
        return out

    def _prove_indices(self, env, eqn, idx_atom, allowed):
        """True iff the index operand's interval(s) fit ``allowed`` (one
        bound per mapped dim).  Uses per-piece concatenate intervals when
        the index vector was stacked from several index arrays."""
        pieces = self.parts(env, idx_atom) if not _is_literal(idx_atom) \
            else None
        if pieces is not None and len(pieces) == len(allowed):
            return all(_lo(p) >= 0 and _hi(p) <= a
                       for p, a in zip(pieces, allowed)), pieces
        iv = self.read(env, idx_atom)
        ok = _lo(iv) >= 0 and _hi(iv) <= min(allowed)
        return ok, [iv]

    def _check_gather(self, env, eqn):
        mode = str(eqn.params.get("mode"))
        operand, indices = eqn.invars[0], eqn.invars[1]
        allowed = self._gather_allowed(eqn)
        proven, ivs = self._prove_indices(env, eqn, indices, allowed)
        if "PROMISE_IN_BOUNDS" in mode and not proven:
            bounded = all(i.lo is not None and i.hi is not None for i in ivs)
            code = "oob-gather-bound" if bounded else "unclamped-gather-index"
            self.flag(code,
                      f"gather index interval {ivs} not within "
                      f"[0, {allowed}] of operand "
                      f"{_aval_shape(operand.aval)} — clamp/mod/mask the "
                      "index or use an explicit OOB mode", "gather")
        out = self.read(env, operand)
        fv = eqn.params.get("fill_value")
        if "FILL" in mode and fv is not None and not proven:
            out = _hull(out, _const_interval(fv))
        self.set(env, eqn.outvars[0], out)

    def _check_scatter(self, env, eqn):
        mode = str(eqn.params.get("mode"))
        operand, indices, updates = eqn.invars[:3]
        if "PROMISE_IN_BOUNDS" in mode:
            dnums = eqn.params["dimension_numbers"]
            op_shape = _aval_shape(operand.aval)
            allowed = [op_shape[d] - 1
                       for d in dnums.scatter_dims_to_operand_dims]
            proven, ivs = self._prove_indices(env, eqn, indices, allowed)
            if not proven:
                bounded = all(i.lo is not None and i.hi is not None
                              for i in ivs)
                code = ("oob-scatter-bound" if bounded
                        else "unclamped-scatter-index")
                self.flag(code,
                          f"scatter index interval {ivs} not within "
                          f"[0, {allowed}] of operand {op_shape} — "
                          "PROMISE_IN_BOUNDS scatters corrupt neighbouring "
                          "table slots on overflow", "scatter")
        op_iv, up_iv = self.read(env, operand), self.read(env, updates)
        if eqn.primitive.name == "scatter":
            self.set(env, eqn.outvars[0], _hull(op_iv, up_iv))
        elif _lo(op_iv) >= 0 and _lo(up_iv) >= 0:
            self.set(env, eqn.outvars[0], Interval(0, None))
        else:
            self.set(env, eqn.outvars[0], TOP)

    def _check_ref_index(self, env, eqn):
        """Prove every dynamic NDIndexer index of a get/swap/addupdate —
        compiled Pallas refs have no OOB semantics at all."""
        tree = eqn.params.get("tree")
        if tree is None:
            return
        import jax.tree_util as jtu
        n = tree.num_leaves
        idx_atoms = list(eqn.invars[len(eqn.invars) - n:]) if n else []
        try:
            indexers = jtu.tree_unflatten(tree, idx_atoms)
        except Exception:
            return
        for indexer in (indexers if isinstance(indexers, (tuple, list))
                        else [indexers]):
            dims = getattr(indexer, "shape", None)
            idx = getattr(indexer, "indices", None)
            if dims is None or idx is None:
                continue
            for d, entry in zip(dims, idx):
                if hasattr(entry, "start") and hasattr(entry, "size"):
                    start, size = entry.start, entry.size
                    if isinstance(start, (int, np.integer)):
                        if start < 0 or start + size > d:
                            self.flag("oob-ref-slice",
                                      f"static ref slice [{start}:"
                                      f"{start + size}] exceeds dim {d}",
                                      eqn.primitive.name)
                    else:
                        iv = self.read(env, start)
                        if not (_lo(iv) >= 0 and _hi(iv) <= d - size):
                            self.flag("unclamped-ref-index",
                                      f"dynamic ref slice start {iv} not "
                                      f"within [0, {d - size}]",
                                      eqn.primitive.name)
                elif isinstance(entry, (int, np.integer)):
                    if not 0 <= int(entry) < d:
                        self.flag("oob-ref-slice",
                                  f"static ref index {int(entry)} outside "
                                  f"dim {d}", eqn.primitive.name)
                elif hasattr(entry, "aval") or _is_literal(entry):
                    iv = self.read(env, entry)
                    if not (_lo(iv) >= 0 and _hi(iv) <= d - 1):
                        self.flag("unclamped-ref-index",
                                  f"dynamic ref index interval {iv} not "
                                  f"within [0, {d - 1}] (refs have no OOB "
                                  "clamping once compiled)",
                                  eqn.primitive.name)

    # ---- the wrap-normalize pattern (negative-index adjustment) ---------- #

    def _wrap_interval(self, env, eqn):
        """jnp indexing emits ``select_n(x < 0, x, x + dim)`` before every
        gather/scatter; recognize it exactly so ``x ∈ [0, d-1]`` stays
        provable through the normalization."""
        if len(eqn.invars) != 3:
            return None
        pred, case0, case1 = eqn.invars
        if _is_literal(pred) or _is_literal(case0):
            return None
        pd = self._defs.get(pred)
        if pd is None or pd.primitive.name != "lt":
            return None
        if pd.invars[0] is not case0 or not _is_literal(pd.invars[1]):
            return None
        if np.asarray(pd.invars[1].val).max(initial=0) != 0 \
                or np.asarray(pd.invars[1].val).min(initial=0) != 0:
            return None
        if _is_literal(case1):
            return None
        cd = self._defs.get(case1)
        if cd is None or cd.primitive.name != "add":
            return None
        k = None
        if cd.invars[0] is case0 and _is_literal(cd.invars[1]):
            k = np.asarray(cd.invars[1].val)
        elif cd.invars[1] is case0 and _is_literal(cd.invars[0]):
            k = np.asarray(cd.invars[0].val)
        if k is None or k.size == 0 or k.min() != k.max() or k.min() <= 0:
            return None
        k = int(k.min())
        x = self.read(env, case0)
        if x.lo is None:
            return None
        if x.lo >= 0:
            return x
        if x.hi is not None and x.hi < 0:
            return _shift(x, k)
        return _mk(min(0, _lo(x) + k), max(_hi(x), k - 1))

    # ---- intrinsics for trusted jnp-library pjits ------------------------ #

    def _pjit_intrinsic(self, env, eqn):
        name = eqn.params.get("name", "")
        if name in ("remainder", "mod"):
            b = self.read(env, eqn.invars[1])
            if _lo(b) >= 1 and b.hi is not None:   # python-sign remainder
                return Interval(0, b.hi - 1)
            return None
        if name == "floor_divide":
            a, b = (self.read(env, v) for v in eqn.invars[:2])
            if _lo(b) >= 1:
                cands = []
                for x in (_lo(a), _hi(a)):
                    for y in (_lo(b), _hi(b)):
                        if x in (NEG, POS) or y == POS:
                            cands.append(x if x in (NEG, POS)
                                         else (0 if x >= 0 else -1))
                        else:
                            cands.append(math.floor(x / y))
                return _mk(min(cands), max(cands))
            return None
        if name in ("searchsorted", "_searchsorted"):
            n = max((_aval_shape(v.aval)[-1] for v in eqn.invars
                     if _aval_shape(v.aval)), default=None)
            if n is not None:
                return Interval(0, n)              # trusted jnp internal
            return TOP
        return None

    # ---- the walk -------------------------------------------------------- #

    def walk(self, jaxpr, env):
        self._defs = getattr(self, "_defs", {})
        for eqn in jaxpr.eqns:
            for v in eqn.outvars:
                if not _is_literal(v):
                    self._defs[v] = eqn
            self._eqn(jaxpr, env, eqn)

    def _default_outs(self, env, eqn):
        for v in eqn.outvars:
            self.set(env, v, TOP)

    def _recurse(self, env, eqn, carry_positions=()):
        """Walk every sub-jaxpr of ``eqn`` with mapped entry intervals;
        returns hulled out intervals per sub (for cond)."""
        hulls = None
        for sub, consts, mapping in _invar_maps(eqn):
            sub_env = dict(env)
            for pos, v in enumerate(sub.invars):
                outer_pos = mapping.get(pos)
                if pos in carry_positions or outer_pos is None \
                        or outer_pos >= len(eqn.invars):
                    self.set(sub_env, v, TOP)
                else:
                    self.set(sub_env, v,
                             self.read(env, eqn.invars[outer_pos]))
            written = _written_positions(sub, self._written_memo)
            for pos in written:
                self.set(sub_env, sub.invars[pos], TOP)
            if consts is not None:
                for v, c in zip(sub.constvars, consts):
                    self.set(sub_env, v, _const_interval(c))
            else:
                for v in sub.constvars:
                    self.set(sub_env, v, self.read(env, v))
            self.walk(sub, sub_env)
            outs = [self.read(sub_env, v) for v in sub.outvars]
            if hulls is None:
                hulls = outs
            else:
                hulls = [_hull(a, b) for a, b in zip(hulls, outs)]
        return hulls

    def _eqn(self, jaxpr, env, eqn):
        self._check_eqn_hygiene(eqn)
        name = eqn.primitive.name
        rd = lambda i: self.read(env, eqn.invars[i])

        if name == "gather":
            self._check_gather(env, eqn)
            return
        if name.startswith("scatter"):
            self._check_scatter(env, eqn)
            return
        if name in ("get", "swap"):
            self._check_ref_index(env, eqn)
            self.set(env, eqn.outvars[0], rd(0))
            return
        if name == "addupdate":
            self._check_ref_index(env, eqn)
            return
        if name == "dynamic_slice":
            self.set(env, eqn.outvars[0], rd(0))     # XLA clamps starts
            return
        if name == "dynamic_update_slice":
            self.set(env, eqn.outvars[0], _hull(rd(0), rd(1)))
            return

        if name in ("jit", "pjit"):
            iv = self._pjit_intrinsic(env, eqn)
            if iv is not None:
                for v in eqn.outvars:
                    self.set(env, v, iv)
                return
            outs = self._recurse(env, eqn)
            if outs is not None:
                for v, o in zip(eqn.outvars, outs):
                    self.set(env, v, o)
            else:
                self._default_outs(env, eqn)
            return
        if name == "cond":
            outs = self._recurse(env, eqn)
            if outs is not None:
                for v, o in zip(eqn.outvars, outs):
                    self.set(env, v, o)
            else:
                self._default_outs(env, eqn)
            return
        if name == "scan":
            nc = eqn.params.get("num_consts", 0)
            ncar = eqn.params.get("num_carry", 0)
            self._recurse(env, eqn,
                          carry_positions=set(range(nc, nc + ncar)))
            self._default_outs(env, eqn)
            return
        if name == "while":
            ncar = len(eqn.outvars)
            bn = eqn.params.get("body_nconsts", 0)
            self._recurse(env, eqn,
                          carry_positions=set(range(bn, bn + ncar)))
            self._default_outs(env, eqn)
            return
        if name == "pallas_call":
            self._recurse(env, eqn)
            self._default_outs(env, eqn)
            return
        if name in ("shard_map", "remat", "remat2", "checkpoint",
                    "custom_jvp_call", "custom_vjp_call", "closed_call",
                    "core_call", "custom_vjp_call_jaxpr"):
            outs = self._recurse(env, eqn)
            if outs is not None and name == "shard_map":
                for v, o in zip(eqn.outvars, outs):
                    self.set(env, v, o)
            else:
                self._default_outs(env, eqn)
            return

        # ---- scalar/elementwise transfer functions ----------------------- #
        if name == "add":
            a, b = rd(0), rd(1)
            self.set(env, eqn.outvars[0], _mk(_lo(a) + _lo(b),
                                              _hi(a) + _hi(b)))
        elif name == "sub":
            a, b = rd(0), rd(1)
            self.set(env, eqn.outvars[0], _mk(_lo(a) - _hi(b),
                                              _hi(a) - _lo(b)))
        elif name == "mul":
            a, b = rd(0), rd(1)
            cands = [_pmul(x, y) for x in (_lo(a), _hi(a))
                     for y in (_lo(b), _hi(b))]
            self.set(env, eqn.outvars[0], _mk(min(cands), max(cands)))
        elif name == "neg":
            a = rd(0)
            self.set(env, eqn.outvars[0], _mk(-_hi(a), -_lo(a)))
        elif name == "max":
            a, b = rd(0), rd(1)
            self.set(env, eqn.outvars[0], _mk(max(_lo(a), _lo(b)),
                                              max(_hi(a), _hi(b))))
        elif name == "min":
            a, b = rd(0), rd(1)
            self.set(env, eqn.outvars[0], _mk(min(_lo(a), _lo(b)),
                                              min(_hi(a), _hi(b))))
        elif name == "clamp":
            lo_iv, _x, hi_iv = rd(0), rd(1), rd(2)
            self.set(env, eqn.outvars[0], _mk(_lo(lo_iv), _hi(hi_iv)))
        elif name == "rem":                      # lax.rem: sign of dividend
            a, b = rd(0), rd(1)
            if _lo(a) >= 0 and _lo(b) >= 1 and b.hi is not None:
                self.set(env, eqn.outvars[0], Interval(0, b.hi - 1))
            elif _lo(b) >= 1 and b.hi is not None:
                self.set(env, eqn.outvars[0],
                         Interval(-(b.hi - 1), b.hi - 1))
            else:
                self._default_outs(env, eqn)
        elif name == "div":                      # lax.div: trunc toward 0
            a, b = rd(0), rd(1)
            if _lo(b) >= 1:
                self.set(env, eqn.outvars[0],
                         _mk(min(0, _lo(a)), max(0, _hi(a))))
            else:
                self._default_outs(env, eqn)
        elif name == "sign":
            self.set(env, eqn.outvars[0], Interval(-1, 1))
        elif name == "select_n":
            wrap = self._wrap_interval(env, eqn)
            if wrap is not None:
                self.set(env, eqn.outvars[0], wrap)
            else:
                self.set(env, eqn.outvars[0],
                         _hull(*[self.read(env, v)
                                 for v in eqn.invars[1:]]))
        elif name == "concatenate":
            ivs = [self.read(env, v) for v in eqn.invars]
            self.set(env, eqn.outvars[0], _hull(*ivs))
        elif name == "iota":
            dim = eqn.params.get("dimension", 0)
            shape = eqn.params.get("shape") or _aval_shape(
                eqn.outvars[0].aval)
            self.set(env, eqn.outvars[0],
                     Interval(0, max(shape[dim] - 1, 0)))
        elif name in ("argmax", "argmin"):
            axes = eqn.params.get("axes", (0,))
            n = _aval_shape(eqn.invars[0].aval)[axes[0]]
            self.set(env, eqn.outvars[0], Interval(0, max(n - 1, 0)))
        elif name == "reduce_sum":
            a = rd(0)
            n = max(1, int(np.prod(_aval_shape(eqn.invars[0].aval))
                           // max(1, int(np.prod(
                               _aval_shape(eqn.outvars[0].aval))))))
            self.set(env, eqn.outvars[0],
                     _mk(min(_pmul(_lo(a), n), _lo(a)),
                         max(_pmul(_hi(a), n), _hi(a))))
        elif name == "cumsum":
            a = rd(0)
            n = _aval_shape(eqn.invars[0].aval)[eqn.params.get("axis", 0)]
            self.set(env, eqn.outvars[0],
                     _mk(min(_pmul(_lo(a), n), _lo(a)),
                         max(_pmul(_hi(a), n), _hi(a))))
        elif name == "sort":
            for v, o in zip(eqn.outvars, eqn.invars):
                self.set(env, v, self.read(env, o))
        elif name == "and":
            a, b = rd(0), rd(1)
            his = [_hi(x) for x in (a, b) if _lo(x) >= 0]
            if his:
                self.set(env, eqn.outvars[0], _mk(0, min(his)))
            else:
                self._default_outs(env, eqn)
        elif name in ("or", "xor"):
            a, b = rd(0), rd(1)
            if _lo(a) >= 0 and _lo(b) >= 0 and a.hi is not None \
                    and b.hi is not None:
                bits = max(int(a.hi).bit_length(), int(b.hi).bit_length())
                self.set(env, eqn.outvars[0], Interval(0, (1 << bits) - 1))
            else:
                self._default_outs(env, eqn)
        elif name in ("program_id", "axis_index", "num_programs"):
            self.set(env, eqn.outvars[0], Interval(0, None))
        elif name.startswith("psum"):
            for v, o in zip(eqn.outvars, eqn.invars):
                iv = self.read(env, o)
                self.set(env, v,
                         Interval(0, None) if _lo(iv) >= 0 else TOP)
        elif name in _PASSTHROUGH:
            for v, o in zip(eqn.outvars, eqn.invars[:len(eqn.outvars)]):
                self.set(env, v, self.read(env, o))
        elif name in _BOOL_OUT:
            self._default_outs(env, eqn)         # dtype default = [0, 1]
        else:
            # unknown primitive: recurse into any sub-jaxpr (so nothing
            # hides a gather from the pass), outputs at dtype default
            self._recurse(env, eqn)
            self._default_outs(env, eqn)


# --------------------------------------------------------------------------- #
# Entry points.
# --------------------------------------------------------------------------- #


def _flat_bounds(args, bounds):
    """Flatten per-argument bounds to the traced fn's flat invar order.
    Each bound is an Interval (broadcast over the arg's leaves), None
    (TOP), or a pytree of Intervals congruent with the arg."""
    import jax
    flat = []
    for a, b in zip(args, bounds):
        n = len(jax.tree_util.tree_leaves(a))
        if b is None:
            flat += [TOP] * n
        elif isinstance(b, Interval):
            flat += [b] * n
        else:
            leaves = jax.tree_util.tree_leaves(
                b, is_leaf=lambda x: isinstance(x, Interval))
            if len(leaves) != n:
                raise ValueError(
                    f"bounds pytree has {len(leaves)} leaves for an "
                    f"argument with {n}")
            flat += [x if isinstance(x, Interval) else TOP for x in leaves]
    return flat


def verify_fn(fn, args, bounds=None, *, name: str) -> list[Finding]:
    """Trace ``fn(*args)`` and statically verify the jaxpr.  ``bounds``
    gives entry intervals per positional argument (see
    :func:`_flat_bounds`); omitted arguments are unbounded.  Returns all
    findings (empty = verified)."""
    import jax
    closed = jax.make_jaxpr(fn)(*args)
    findings: list[Finding] = []
    an = _Analyzer(findings, name)
    env: dict = {}
    if bounds is None:
        bounds = [None] * len(args)
    flat = _flat_bounds(args, bounds)
    if len(flat) != len(closed.jaxpr.invars):
        raise ValueError(f"{name}: {len(flat)} bound leaves for "
                         f"{len(closed.jaxpr.invars)} traced inputs")
    for v, iv in zip(closed.jaxpr.invars, flat):
        an.set(env, v, iv)
    for v, c in zip(closed.jaxpr.constvars, closed.consts):
        an.set(env, v, _const_interval(c))
    an.walk(closed.jaxpr, env)
    return findings


def routing_bounds():
    """A ``RoutingState`` of entry intervals built from
    :data:`FIELD_BOUNDS` — the verifier's table assumptions, identical to
    what the plan validator enforces on every wire payload."""
    from repro.core.routing_table import RoutingState

    def iv(field, default=TOP):
        b = FIELD_BOUNDS.get(field)
        return Interval(*b) if b else default

    return RoutingState(
        svc_rule_start=iv("svc_rule_start"),
        svc_rule_count=iv("svc_rule_count"),
        rule_field=iv("rule_field"),
        rule_value=iv("rule_value"),
        rule_cluster=iv("rule_cluster"),
        cluster_ep_start=iv("cluster_ep_start"),
        cluster_ep_count=iv("cluster_ep_count"),
        cluster_policy=iv("cluster_policy"),
        ep_instance=iv("ep_instance"),
        ep_weight=Interval(0, None),
        ep_drained=iv("ep_drained"),
        maglev_table=iv("maglev_table"),
        ep_load=iv("ep_load"),
        ep_inflight_ewma=Interval(0, None),
        ep_tput_ewma=Interval(0, None),
        rr_cursor=iv("rr_cursor"),
        aff_key=iv("aff_key"),
        aff_ep=iv("aff_ep"),
        version=Interval(0, None),
    )


# --------------------------------------------------------------------------- #
# The kernel sweep — representative shapes from kernels/tune.py, every
# registered policy in the table, both folds.
# --------------------------------------------------------------------------- #

SWEEP_R, SWEEP_I, SWEEP_C = 64, 18, 4
FOLDS = ("segment", "onehot")


def _sweep_state():
    """One 3-lane cluster per registered policy (rule: feature 0 == enum
    routes to it), so every ``kernel_offset`` lowering is live in the
    trace and a newly registered policy is swept automatically."""
    from repro.core import policy_defs
    from repro.core.routing_table import Cluster, Rule, ServiceConfig, \
        build_state

    services, clusters = [], []
    for p in policy_defs.REGISTRY:
        eps = [(3 * p.enum + j) % SWEEP_I for j in range(3)]
        clusters.append(Cluster(f"c_{p.name}", endpoints=eps, policy=p.enum))
        services.append(ServiceConfig(
            f"s_{p.name}", rules=[Rule(0, str(p.enum), f"c_{p.name}")]))
    state, names = build_state(services, clusters)
    return state


def _admit_args(commit: bool):
    import jax.numpy as jnp
    from repro.core.routing_table import MAX_EPS_PER_CLUSTER, N_FEATURES

    R, I, C = SWEEP_R, SWEEP_I, SWEEP_C
    state = _sweep_state()
    rid = jnp.arange(R, dtype=jnp.int32)
    z = jnp.zeros((R,), jnp.int32)
    feats = jnp.zeros((R, N_FEATURES), jnp.int32)
    gum = jnp.zeros((R, MAX_EPS_PER_CLUSTER), jnp.float32)
    head = [rid, z, feats, z]
    head_b = [None, None, None, Interval(0, None)]
    if commit:
        pool = [jnp.full((I, C), -1, jnp.int32),
                jnp.full((I, C), -1, jnp.int32),
                jnp.zeros((I, C), jnp.int32), jnp.zeros((I, C), jnp.int32),
                jnp.zeros((I, C), jnp.int32), jnp.zeros((I, C), jnp.int32)]
        args = head + [z, state] + pool + [z, gum]
        bounds = head_b + [None, routing_bounds()] + [None] * 6 \
            + [Interval(0, None), None]
    else:
        free = jnp.ones((I, C), jnp.int32)
        args = head + [state, free] + [z, gum]
        bounds = head_b + [routing_bounds(), Interval(0, 1),
                           Interval(0, None), None]
    return args, bounds


def _complete_args():
    import jax.numpy as jnp
    from repro.core.routing_table import MAX_ENDPOINTS, MAX_SERVICES

    I, C = SWEEP_I, SWEEP_C
    pool = [jnp.full((I, C), -1, jnp.int32), jnp.full((I, C), -1, jnp.int32),
            jnp.zeros((I, C), jnp.int32), jnp.zeros((I, C), jnp.int32),
            jnp.zeros((I, C), jnp.int32), jnp.ones((I, C), jnp.int32)]
    nxt = jnp.zeros((I, C), jnp.int32)
    load = jnp.zeros((MAX_ENDPOINTS,), jnp.int32)
    rx = jnp.zeros((MAX_SERVICES,), jnp.int32)
    ewl = jnp.zeros((MAX_ENDPOINTS,), jnp.float32)
    ewt = jnp.zeros((MAX_ENDPOINTS,), jnp.float32)
    args = pool + [nxt, load, rx, ewl, ewt]
    bounds = [None] * 7 + [Interval(0, None), Interval(0, None),
                           Interval(0, None), Interval(0, None)]
    return args, bounds


def verify_kernels(folds=FOLDS) -> list[Finding]:
    """Statically verify every registered datapath kernel × fold on the
    representative sweep shapes.  Empty list = all proven."""
    import functools

    import jax
    import numpy as np_
    from jax.sharding import Mesh

    from repro.kernels import completion as _cp
    from repro.kernels import route_match as _rm
    from repro.kernels import shard_admit as _sa
    from repro.core.routing_table import N_FEATURES

    findings: list[Finding] = []
    for fold in folds:
        args, bounds = _admit_args(commit=False)
        findings += verify_fn(
            functools.partial(_rm.admit, block_r=SWEEP_R, fold=fold,
                              interpret=True),
            args, bounds, name=f"admit[{fold}]")
        args, bounds = _admit_args(commit=True)
        findings += verify_fn(
            functools.partial(_rm.admit_commit, block_r=SWEEP_R, fold=fold,
                              interpret=True),
            args, bounds, name=f"admit_commit[{fold}]")
        args, bounds = _complete_args()
        findings += verify_fn(
            functools.partial(_cp.complete, eos=1, max_len=16, block_i=2,
                              fold=fold, interpret=True),
            args, bounds, name=f"complete[{fold}]")

    # route_match building block (least-request scan only)
    import jax.numpy as jnp
    state = _sweep_state()
    svc = jnp.zeros((SWEEP_R,), jnp.int32)
    feats = jnp.zeros((SWEEP_R, N_FEATURES), jnp.int32)
    findings += verify_fn(
        functools.partial(_rm.route_match, block_r=SWEEP_R, interpret=True),
        (svc, feats, state), (None, None, routing_bounds()),
        name="route_match")

    # staged policy chain (every staged_offset lowering in one trace)
    from repro.core import policies as _pol
    cluster = jnp.zeros((SWEEP_R,), jnp.int32)
    key = jax.random.PRNGKey(0)
    findings += verify_fn(
        lambda st, cl, k, f: _pol.select(st, cl, k, f),
        (state, cluster, key, feats),
        (routing_bounds(), None, None, None), name="policies.select")

    # sharded admit relay on a 1-device mesh (collectives + relay hop)
    mesh = Mesh(np_.asarray(jax.devices()[:1]), ("shard",))
    args, bounds = _admit_args(commit=True)
    (rid, z, feats2, mb, tok, st), pool = args[:6], args[6:12]
    rnd, gum = args[12], args[13]
    findings += verify_fn(
        functools.partial(_sa.admit_commit_sharded, mesh=mesh,
                          block_r=SWEEP_R, fold="segment", interpret=True),
        (rid, z, feats2, mb, tok, st, *pool, rnd, gum),
        (None, None, None, Interval(0, None), Interval(0, None),
         routing_bounds(), *([None] * 6), Interval(0, None), None),
        name="admit_commit_sharded[segment]")
    return findings


# --------------------------------------------------------------------------- #
# PolicyDef registry checks — the four-lowering contract.
# --------------------------------------------------------------------------- #

REQUIRED_HOOKS = ("kernel_offset", "oracle_pick", "staged_offset",
                  "host_pick")
VALID_MERGES = ("cursor", "waterfill", "none")


def check_registry() -> list[Finding]:
    """Every registered policy carries all four lowering hooks, a valid
    shard-merge rule, and a unique enum."""
    from repro.core import policy_defs

    findings: list[Finding] = []
    seen: dict[int, str] = {}
    for p in policy_defs.REGISTRY:
        where = f"registry/{p.name}"
        for hook in REQUIRED_HOOKS:
            fn = getattr(p, hook, None)
            if not callable(fn):
                findings.append(Finding(
                    "policy-missing-hook", where,
                    f"policy {p.name!r} lacks a callable {hook!r} — all "
                    "four datapath lowerings must be registered"))
        merge = getattr(p, "shard_merge", None)
        if merge not in VALID_MERGES:
            findings.append(Finding(
                "policy-bad-merge", where,
                f"shard_merge {merge!r} not one of {VALID_MERGES} — the "
                "sharded reconciliation cannot carry this policy's state"))
        if p.enum in seen:
            findings.append(Finding(
                "policy-dup-enum", where,
                f"enum {p.enum} already registered by {seen[p.enum]!r}"))
        seen.setdefault(p.enum, p.name)
    return findings


def verify_all() -> list[Finding]:
    """The full static pass: registry contract + every kernel × fold."""
    return check_registry() + verify_kernels()
