"""Staged engine runtime: compile-once, run-many execution of ISA programs.

The software engines used to re-trace (and re-XLA-compile) every program
they ran — ~15-20 s per random program for the shard_map LaneEngine — so
cross-engine differential coverage was priced per *program*. This module
makes execution cost per *signature* instead, the software analogue of
Ara's one-issue-many-elements amortization (§III-E2, §IV):

- :func:`resolve_vtype` — the host-side pre-pass. Walks a program once,
  legality-checks every instruction via ``isa.check_insn`` (hoisted out of
  the traced execution loop — both engines and the scoreboard share it),
  and resolves the per-instruction vtype (vl, sew, lmul) that ``VSETVL``
  establishes, since VSETVL operands are static program text.
- :func:`encode_program` — lowers a program into a structure-of-arrays
  instruction table: one int32 row per instruction (opcode id, register
  bases, scalar reg, address/stride/amount/nf immediates, resolved
  vl/vpr/lmul/sew). ``VSETVL`` disappears here — its effect is baked into
  every row.
- :class:`Signature` — the static shape key of an encoded batch: engine
  kind, lanes, register-file slots, padded memory words, padded program
  length, batch size, storage dtype. Two programs with the same signature
  run through the same compiled executable; opcodes, operands and vtype
  are *data*.
- :class:`TraceCache` — an LRU of compiled executables keyed by
  Signature, shared by ``ReferenceEngine`` and ``LaneEngine`` (module
  default :data:`TRACE_CACHE`), with hit/miss/compile counters tests and
  benchmarks can assert on.
- :func:`build_runner` — builds the one jitted executable per signature:
  a ``lax.scan`` over instruction rows whose step is a ``lax.switch``
  over opcodes, ``vmap``-batched over programs, wrapped in ``shard_map``
  for the lane engine, with memory/scalar buffers donated.

Program and memory lengths are padded to buckets (``NOP`` rows, zero
words) so near-miss shapes share executables; the true memory size is
per-program *data*, which keeps the index-clamp and store-bounds
semantics exact on padded buffers.
"""
from __future__ import annotations

import collections
import dataclasses

import jax
import jax.numpy as jnp
from jax import shard_map as _shard_map
import numpy as np

from repro.core import isa
from repro.core.precision import SEW_TO_DTYPE

NF_MAX = max(isa.LMULS)          # nf * lmul <= 8 caps fields at 8

# Opcode table: VGATHER and VLUXEI share semantics (and a branch); VSETVL
# has no row (the pre-pass folds it into every row's vl/vpr/lmul/sew).
# The integer/fixed-point class (vadd..vsmul) executes on an int32 view
# of the registers; the saturating four carry the sticky vxsat flag.
OPS = ("nop", "vld", "vlds", "vgather", "vlseg", "vst", "vsseg", "vsuxei",
       "vfma", "vfma_vs", "vfadd", "vfmul", "vfwmul", "vfwma", "vfncvt",
       "vadd", "vins", "vext", "vslide", "ldscalar",
       "vsub", "vmul", "vsaddu", "vsadd", "vssub", "vsmul",
       "vmseq", "vmsne", "vmslt", "vmsle", "vmfeq", "vmflt",
       "vmand", "vmor", "vmxor", "vmerge",
       "vredsum", "vredmax", "vredmin", "vfwredsum")
OP_ID = {name: i for i, name in enumerate(OPS)}

# Instruction-table columns (all int32):
#   op    opcode id                  rd   dest/store-source group base
#   ra    source group base (va / vs / vidx)
#   rb    second source group base (vb)
#   sd    scalar register id         imm  element address
#   aux   stride / slide amount / extract index / nf
#   vl    resolved vector length     vpr  per-register capacity at sew
#   lmul  registers per group (group_span: 1 for fractional LMUL)
#   sewi/wsewi  SEWS index of sew / 2*sew
#   vm    RVV mask bit: 1 unmasked (default), 0 masked by v0 — one more
#         int32 data column, so masking never perturbs the signature
FIELDS = ("op", "rd", "ra", "rb", "sd", "imm", "aux",
          "vl", "vpr", "lmul", "sewi", "wsewi", "vm")

_NOP_DEFAULTS = {"vpr": 1, "lmul": 1, "vm": 1}   # keep // and % well-defined

_SEW_DTYPE = {bits: jnp.dtype(name) for bits, name in SEW_TO_DTYPE.items()}

_OP_FOR = {
    isa.VLD: "vld", isa.VLDS: "vlds", isa.VGATHER: "vgather",
    isa.VLUXEI: "vgather", isa.VLSEG: "vlseg", isa.VST: "vst",
    isa.VSSEG: "vsseg", isa.VSUXEI: "vsuxei", isa.VFMA: "vfma",
    isa.VFMA_VS: "vfma_vs", isa.VFADD: "vfadd", isa.VFMUL: "vfmul",
    isa.VFWMUL: "vfwmul", isa.VFWMA: "vfwma", isa.VFNCVT: "vfncvt",
    isa.VADD: "vadd", isa.VSUB: "vsub", isa.VMUL: "vmul",
    isa.VSADDU: "vsaddu", isa.VSADD: "vsadd", isa.VSSUB: "vssub",
    isa.VSMUL: "vsmul", isa.VINS: "vins", isa.VEXT: "vext",
    isa.VSLIDE: "vslide", isa.LDSCALAR: "ldscalar",
    isa.VMSEQ: "vmseq", isa.VMSNE: "vmsne", isa.VMSLT: "vmslt",
    isa.VMSLE: "vmsle", isa.VMFEQ: "vmfeq", isa.VMFLT: "vmflt",
    isa.VMAND: "vmand", isa.VMOR: "vmor", isa.VMXOR: "vmxor",
    isa.VMERGE: "vmerge", isa.VREDSUM: "vredsum",
    isa.VREDMAX: "vredmax", isa.VREDMIN: "vredmin",
    isa.VFWREDSUM: "vfwredsum",
}


def bucket(n: int, step: int = 8) -> int:
    """Round ``n`` up to a multiple of ``step`` (minimum one bucket)."""
    return max(step, -(-n // step) * step)


def bucket_pow2(n: int, lo: int = 64) -> int:
    """Round ``n`` up to a power of two (memory padding granularity)."""
    w = lo
    while w < n:
        w *= 2
    return w


# ---------------------------------------------------------------------------
# host pre-pass: legality + vtype resolution (shared with the scoreboard)
# ---------------------------------------------------------------------------


def resolve_vtype(program, vlmax64: int, lint: bool = False,
                  mem_words=None):
    """Legality-check a program once and resolve its per-insn vtype.

    Returns ``[(ins, vl, sew, lmul), ...]`` with VSETVL rows carrying the
    vtype they establish. Raises ``isa.IllegalInstruction`` (a
    ValueError carrying code/mnemonic/vtype/index) on the first illegal
    instruction — on the host, before anything is traced or executed;
    both engines and ``simulate_timing`` run this exact pre-pass.

    ``lint=True`` additionally runs the whole-program static analyzer
    (``core/analysis.py``) first and raises ``analysis.LintError`` on any
    E-class finding (def-before-use, wide-group clobber, v0 clobber,
    static OOB footprints when ``mem_words`` is given). The lint pass is
    pure host python — it never touches the trace cache or changes what
    XLA compiles, so enabling it keeps the differential grid's
    compiles == 2 contract intact.
    """
    if lint:
        from repro.core import analysis
        analysis.assert_clean(program, vlmax64, mem_words=mem_words)
    out = []
    vl, sew, lmul = vlmax64, 64, 1
    for i, ins in enumerate(program):
        isa.check_insn(ins, sew, lmul, index=i)
        if type(ins) is isa.VSETVL:
            sew, lmul = ins.sew, ins.lmul
            vl = isa.vsetvl_grant(ins.vl, vlmax64, sew, lmul)
        out.append((ins, vl, sew, lmul))
    return out


def encode_program(program, vlmax64: int):
    """Lower a program to instruction-table rows (list of field dicts)."""
    rows = []
    for ins, vl, sew, lmul in resolve_vtype(program, vlmax64):
        t = type(ins)
        if t is isa.VSETVL:
            continue
        name = _OP_FOR.get(t)
        if name is None:
            raise ValueError(ins)
        r = dict.fromkeys(FIELDS, 0)
        r.update(op=OP_ID[name], vl=vl, vpr=vlmax64 * (64 // sew),
                 lmul=isa.group_span(lmul), sewi=isa.SEWS.index(sew),
                 wsewi=isa.SEWS.index(2 * sew) if 2 * sew in isa.SEWS else 0,
                 vm=getattr(ins, "vm", 1))
        if t in (isa.VLD, isa.VLDS, isa.VGATHER, isa.VLUXEI, isa.VLSEG):
            r["rd"], r["imm"] = ins.vd, ins.addr
            if t is isa.VLDS:
                r["aux"] = ins.stride
            elif t is isa.VLSEG:
                r["aux"] = ins.nf
            elif t is not isa.VLD:
                r["ra"] = ins.vidx
        elif t in (isa.VST, isa.VSSEG, isa.VSUXEI):
            r["rd"], r["imm"] = ins.vs, ins.addr
            if t is isa.VSSEG:
                r["aux"] = ins.nf
            elif t is isa.VSUXEI:
                r["ra"] = ins.vidx
        elif t in (isa.VFMA, isa.VFADD, isa.VFMUL, isa.VADD, isa.VSUB,
                   isa.VMUL, isa.VSADDU, isa.VSADD, isa.VSSUB, isa.VSMUL,
                   isa.VFWMUL, isa.VFWMA):
            r["rd"], r["ra"], r["rb"] = ins.vd, ins.va, ins.vb
        elif t is isa.VFMA_VS:
            r["rd"], r["sd"], r["rb"] = ins.vd, ins.vs_scalar, ins.vb
        elif t is isa.VFNCVT:
            r["rd"], r["ra"] = ins.vd, ins.vs
        elif t is isa.VINS:
            r["rd"], r["sd"] = ins.vd, ins.scalar
        elif t is isa.VEXT:
            r["sd"], r["ra"], r["aux"] = ins.sd, ins.vs, ins.idx
        elif t is isa.VSLIDE:
            r["rd"], r["ra"], r["aux"] = ins.vd, ins.vs, ins.amount
        elif t is isa.LDSCALAR:
            r["sd"], r["imm"] = ins.sd, ins.addr
        elif t in (isa.VMSEQ, isa.VMSNE, isa.VMSLT, isa.VMSLE, isa.VMFEQ,
                   isa.VMFLT, isa.VMAND, isa.VMOR, isa.VMXOR, isa.VMERGE):
            r["rd"], r["ra"], r["rb"] = ins.vd, ins.va, ins.vb
        elif t in isa._REDUCTIONS:
            r["rd"], r["ra"] = ins.vd, ins.vs
        rows.append(r)
    return rows


def pack_tables(tables, pad_to=None):
    """Stack per-program row lists into an (N, P) SoA batch, NOP-padded.

    ``P`` is bucketed so programs of nearby length share a signature.
    """
    p = pad_to or bucket(max([len(t) for t in tables] + [1]))
    out = {}
    for f in FIELDS:
        a = np.full((len(tables), p), _NOP_DEFAULTS.get(f, 0), np.int32)
        for i, rows in enumerate(tables):
            if rows:
                a[i, :len(rows)] = [r[f] for r in rows]
        out[f] = a
    return out


# ---------------------------------------------------------------------------
# trace cache
# ---------------------------------------------------------------------------


def mesh_fingerprint(mesh, axes) -> tuple:
    """The full topology identity of a mesh: per-axis (name, size) pairs
    in nesting order, plus the device order. Two meshes with the same
    TOTAL device count but different shapes — a 4-lane flat mesh and a
    2×2 clusters×lanes mesh, or a 2×4 and a 4×2 cluster grid — must
    produce distinct fingerprints, or the trace cache would replay an
    executable whose psum/pmax reconciliation was compiled for the
    wrong axis nesting."""
    return (tuple((a, int(mesh.shape[a])) for a in axes),
            tuple(d.id for d in np.asarray(mesh.devices).ravel()))


@dataclasses.dataclass(frozen=True)
class Signature:
    """Static shape key of an encoded batch — everything XLA specializes
    on. Programs differing only in opcodes/operands/vtype share one."""
    kind: str            # "ref" | "lane" | "cluster"
    lanes: int           # TOTAL lanes across all clusters
    slots: int           # per-lane element slots per vector register
    window: int          # global flat element window (>= the batch max vl)
    mem_words: int       # padded memory words
    prog_len: int        # padded instruction rows
    batch: int
    storage: str         # canonical dtype name
    mesh_key: tuple = ()  # mesh_fingerprint(): axes+sizes, device order
    clusters: int = 1    # mesh nesting: lanes are grouped clusters-ways


@dataclasses.dataclass
class CacheStats:
    hits: int = 0
    misses: int = 0
    compiles: int = 0    # actual traces (counts silent retraces too)

    def snapshot(self) -> "CacheStats":
        return dataclasses.replace(self)

    def reset(self):
        self.hits = self.misses = self.compiles = 0


class TraceCache:
    """LRU cache of compiled signature executables.

    One instance (module default :data:`TRACE_CACHE`) is shared by both
    engines, so a ReferenceEngine and a LaneEngine sized alike still get
    distinct entries (``kind`` is in the key) while repeated runs of
    either reuse theirs. ``stats.compiles`` is bumped at *trace* time
    inside the built executable, so tests can assert that same-signature
    programs really do reuse the compiled step function.
    """

    def __init__(self, maxsize: int = 64):
        self.maxsize = maxsize
        self.stats = CacheStats()
        self._fns = collections.OrderedDict()

    def __len__(self):
        return len(self._fns)

    def get(self, sig: Signature, builder):
        fn = self._fns.get(sig)
        if fn is not None:
            self.stats.hits += 1
            self._fns.move_to_end(sig)
            return fn
        self.stats.misses += 1
        fn = builder()
        self._fns[sig] = fn
        while len(self._fns) > self.maxsize:
            self._fns.popitem(last=False)
        return fn

    def clear(self):
        self._fns.clear()


TRACE_CACHE = TraceCache()


# ---------------------------------------------------------------------------
# integer / fixed-point arithmetic (int32 view of the registers)
# ---------------------------------------------------------------------------


def _u32(x):
    return jax.lax.bitcast_convert_type(x, jnp.uint32)


def _i32(x):
    return jax.lax.bitcast_convert_type(x, jnp.int32)


def wrap_int(x, bits: int):
    """int32 -> signed two's-complement ``bits``-wide value (sign-extend)."""
    if bits >= 32:
        return x
    sh = 32 - bits
    return (x << sh) >> sh                   # jnp shifts: arithmetic right


def int_arith(kind: str, a, b, bits: int):
    """One integer/fixed-point op on int32 canonical values.

    ``bits`` is static (the lax.switch over sewi specializes it); returns
    ``(result int32, saturated bool)``. vadd/vsub/vmul wrap mod 2^bits;
    the saturating four clamp and flag. vxrm is fixed at rnu: VSMUL adds
    2^(bits-2) before the arithmetic (bits-1)-shift — ties round up.
    SEW=32 needs care in a 32-bit trace: overflow is detected by sign
    algebra for add/sub, the unsigned view for vsaddu, and VSMUL's 64-bit
    product is rebuilt from 16-bit partial products in uint32.
    """
    s = min(bits, 32)                        # the SEW=64 branch never runs
    lo, hi = -(1 << (s - 1)), (1 << (s - 1)) - 1
    no_sat = jnp.zeros(a.shape, bool)
    if kind == "vadd":
        return wrap_int(a + b, s), no_sat
    if kind == "vsub":
        return wrap_int(a - b, s), no_sat
    if kind == "vmul":
        return wrap_int(a * b, s), no_sat
    if s < 32:                               # everything fits one int32
        if kind == "vsaddu":
            um = (1 << s) - 1
            r0 = (a & um) + (b & um)
            return wrap_int(jnp.minimum(r0, um), s), r0 > um
        if kind == "vsadd":
            r0 = a + b
        elif kind == "vssub":
            r0 = a - b
        else:                                # vsmul, rnu rounding
            r0 = (a * b + (1 << (s - 2))) >> (s - 1)
        r = jnp.clip(r0, lo, hi)
        return r, r != r0
    if kind == "vsadd":
        r0 = a + b
        ovf = ((a ^ r0) & (b ^ r0)) < 0
        return jnp.where(ovf, jnp.where(a < 0, lo, hi), r0), ovf
    if kind == "vssub":
        r0 = a - b
        ovf = ((a ^ b) & (a ^ r0)) < 0
        return jnp.where(ovf, jnp.where(a < 0, lo, hi), r0), ovf
    if kind == "vsaddu":
        ua, ub = _u32(a), _u32(b)
        r0 = ua + ub
        sat = r0 < ua
        return _i32(jnp.where(sat, jnp.uint32(0xFFFFFFFF), r0)), sat
    # vsmul at SEW=32: signed 64-bit product via 16x16 partial products
    ua, ub = _u32(a), _u32(b)
    al, ah = ua & 0xFFFF, ua >> 16
    bl, bh = ub & 0xFFFF, ub >> 16
    t1 = ah * bl + ((al * bl) >> 16)
    t2 = al * bh + (t1 & 0xFFFF)
    uhigh = ah * bh + (t1 >> 16) + (t2 >> 16)
    high = _i32(uhigh) - jnp.where(a < 0, b, 0) - jnp.where(b < 0, a, 0)
    ulow = ua * ub
    low2 = ulow + jnp.uint32(1 << 30)        # + rnu half (2^(s-2))
    high2 = high + (low2 < ulow).astype(jnp.int32)
    r0 = (high2 << 1) | _i32(low2 >> 31)     # (prod + 2^30) >> 31
    minmin = (a == lo) & (b == lo)           # the only overflowing input
    return jnp.where(minmin, hi, r0), minmin


# opcode -> (kind, sets-vxsat) for the integer branch
INT_OPS = {"vadd": ("vadd", False), "vsub": ("vsub", False),
           "vmul": ("vmul", False), "vsaddu": ("vsaddu", True),
           "vsadd": ("vsadd", True), "vssub": ("vssub", True),
           "vsmul": ("vsmul", True)}


# ---------------------------------------------------------------------------
# the staged interpreter: scan over rows, switch over opcodes
# ---------------------------------------------------------------------------


def build_runner(sig: Signature, stats: CacheStats, mesh=None,
                 axis: str = None, axes: tuple = None):
    """Compile the one executable for ``sig``.

    Returns ``fn(mems, svecs, sizes, rows) -> (mems, svecs)`` where
    ``mems`` is (batch, mem_words), ``svecs`` (batch, 32), ``sizes``
    (batch,) true memory words, and ``rows`` the packed (batch, prog_len)
    instruction table. Lane-sharded when ``mesh``/``axis`` are given
    (memory replicated, reconciled through psum — the VLSU as the single
    all-lane unit), single-device otherwise: both engines share this one
    step definition, so their semantics cannot drift.

    ``axes`` selects the HIERARCHICAL topology (the ClusterEngine): a
    ``(clusters_axis, lanes_axis)`` pair naming a 2-D mesh whose outer
    axis groups ``sig.clusters`` clusters of ``lanes/clusters`` lanes.
    The staged step is unchanged per-lane — a lane's global index is
    ``cluster * lanes_per_cluster + lane_in_cluster`` — and every
    reconciliation (VLSU scatter counts, SLDU slide/extract gathers,
    reduction-window scatters, the sticky vxsat pmax) folds
    intra-cluster first, then across clusters. The contributions are
    disjoint per lane, so the two-stage fold is bit-identical to the
    flat one — the hierarchy models AraXL's cluster interconnect
    without perturbing the differential contract.

    Element layout per lane: local flat-group slot ``p`` of a register
    group holds global element ``lane + p * lanes`` (the interleaved VRF
    partition of §III-E2; with lanes=1 this degenerates to the identity,
    which *is* the reference engine).
    """
    lanes = sig.lanes
    slots = sig.slots                      # per-register slots per lane
    gwin = sig.window                      # global element window
    window = gwin // lanes                 # flat group window per lane
    storage = jnp.dtype(sig.storage)
    nregs = isa.NUM_VREGS
    int_storage = jnp.issubdtype(storage, jnp.integer)
    # largest int32 the storage represents exactly: f32's 24-bit mantissa
    # caps it below INT32_MAX, so float->int casts clip there and stay
    # deterministic across backends (NaN pins to 0 for the same reason)
    i32max = (2 ** 31 - 1) if (int_storage or storage.itemsize >= 8) \
        else 2 ** 31 - 128
    # reduction tree: static pow2 fold window and per-sewi max/min
    # identities (float formats use +-inf; the SEW=8 / fixed-point
    # integer lanes use the type extremes so identities survive qdyn)
    RED_P = 1 << max(gwin - 1, 0).bit_length()
    if int_storage:
        MAX_IDENT = jnp.array(
            [-(1 << (min(b, 32) - 1)) for b in isa.SEWS], storage)
        MIN_IDENT = jnp.array(
            [(1 << (min(b, 32) - 1)) - 1 for b in isa.SEWS], storage)
    else:
        MAX_IDENT = jnp.array(
            [-jnp.inf, -jnp.inf, -jnp.inf, -128.0], storage)
        MIN_IDENT = jnp.array(
            [jnp.inf, jnp.inf, jnp.inf, 127.0], storage)

    def to_int(x):
        """Storage value -> int32 two's-complement canonical form."""
        if int_storage:
            return x
        x = jnp.where(jnp.isnan(x), jnp.zeros_like(x), x)
        return jnp.clip(x, -(2.0 ** 31), float(i32max)).astype(jnp.int32)

    def _q(x, bits):
        # HW-width rounding. Float storage: round to the SEW float format
        # (identity when >= storage width), except SEW=8 — the integer
        # lane — which truncates-and-wraps to int8. Integer storage makes
        # the engine an exact fixed-point machine: every width wraps.
        if int_storage:
            return wrap_int(x, min(bits, 32))
        if bits == 8:
            return wrap_int(to_int(x), 8).astype(storage)
        dt = _SEW_DTYPE[bits]
        if dt.itemsize >= storage.itemsize:
            return x
        return x.astype(dt).astype(storage)

    def qdyn(x, sewi):
        return jax.lax.switch(
            sewi, [lambda y, b=b: _q(y, b) for b in isa.SEWS], x)

    def one_program(mem, s, size, rows):
        stats.compiles += 1                # trace-time side effect
        if axes:
            # clusters × lanes-per-cluster nesting: the global lane id
            # concatenates cluster blocks, so cluster c owns the lane
            # range [c*lpc, (c+1)*lpc)
            lpc = lanes // sig.clusters
            lane = jax.lax.axis_index(axes[0]) * lpc \
                + jax.lax.axis_index(axes[1])
        else:
            lane = jax.lax.axis_index(axis) if axis else 0
        e = jnp.arange(window)
        ids = lane + e * lanes             # global element id per slot

        def allsum(x):
            if axes:
                # hierarchical reconciliation: intra-cluster ring first
                # (the cheap local interconnect), then the inter-cluster
                # stage — bit-exact either way (disjoint contributions)
                return jax.lax.psum(jax.lax.psum(x, axes[1]), axes[0])
            return jax.lax.psum(x, axis) if axis else x

        def allmax(x):
            if axes:
                return jax.lax.pmax(jax.lax.pmax(x, axes[1]), axes[0])
            return jax.lax.pmax(x, axis) if axis else x

        def step(carry, row):
            v, mem, s = carry
            vl = row["vl"]
            spr = jnp.maximum(row["vpr"] // lanes, 1)  # slots/reg/lane
            mask = ids < vl

            def R(v, base):
                r = jnp.clip(base + e // spr, 0, nregs - 1)
                return v[r, e % spr]

            def W(v, base, vals, ok=None):
                ok = mask if ok is None else ok
                r = jnp.where(ok, base + e // spr, nregs)
                return v.at[r, e % spr].set(vals, mode="drop")

            # the active body: mask-undisturbed predication off the v0
            # group (element active iff nonzero); vm=1 degenerates to the
            # plain body so unmasked rows cost one select, not a branch
            act = jnp.where(row["vm"] == 0,
                            mask & (R(v, isa.MASK_REG) != 0), mask)

            def mstore(mem, gidx, vals, ok):
                # VLSU collect: scatter the valid contributions, count
                # writers per address, reconcile across lanes via psum
                gi = jnp.where(ok, gidx, 0)
                add = jnp.where(ok, vals, 0).astype(storage)
                upd = allsum(jnp.zeros_like(mem).at[gi].add(add))
                cnt = allsum(jnp.zeros(mem.shape, jnp.int32).at[gi].add(
                    ok.astype(jnp.int32)))
                return jnp.where(cnt > 0, upd, mem)

            def op_nop(v, mem, s):
                return v, mem, s

            def op_vld(v, mem, s):
                idx = jnp.where(act, row["imm"] + ids, 0)
                return (W(v, row["rd"], qdyn(mem[idx], row["sewi"]), act),
                        mem, s)

            def op_vlds(v, mem, s):
                idx = jnp.where(act, row["imm"] + row["aux"] * ids, 0)
                return (W(v, row["rd"], qdyn(mem[idx], row["sewi"]), act),
                        mem, s)

            def op_vgather(v, mem, s):
                # OOB indexed loads are UB in HW; the model pins them to
                # the *true* memory edges (size is data, not padding)
                iv = R(v, row["ra"]).astype(jnp.int32)
                gi = jnp.clip(jnp.where(act, row["imm"] + iv, 0),
                              0, size - 1)
                return (W(v, row["rd"], qdyn(mem[gi], row["sewi"]), act),
                        mem, s)

            def op_vlseg(v, mem, s):
                nf = row["aux"]
                for f in range(NF_MAX):
                    ok = mask & (f < nf)
                    idx = jnp.where(ok, row["imm"] + nf * ids + f, 0)
                    v = W(v, row["rd"] + f * row["lmul"],
                          qdyn(mem[idx], row["sewi"]), ok)
                return v, mem, s

            def op_vst(v, mem, s):
                gi = row["imm"] + ids
                return v, mstore(mem, gi, R(v, row["rd"]),
                                 act & (gi < size)), s

            def op_vsseg(v, mem, s):
                nf = row["aux"]
                for f in range(NF_MAX):
                    gi = row["imm"] + f + nf * ids
                    ok = mask & (f < nf) & (gi < size)
                    mem = mstore(mem, gi,
                                 R(v, row["rd"] + f * row["lmul"]), ok)
                return v, mem, s

            def op_vsuxei(v, mem, s):
                # highest element wins: find each address's winning
                # element id globally (pmax), then contribute only it
                iv = R(v, row["ra"]).astype(jnp.int32)
                gi = jnp.clip(jnp.where(act, row["imm"] + iv, 0),
                              0, size - 1)
                eid = jnp.where(act, ids, -1).astype(jnp.int32)
                order = allmax(
                    jnp.full(mem.shape, -1, jnp.int32).at[gi].max(eid))
                win = act & (order[gi] == ids)
                contrib = allsum(
                    jnp.zeros_like(mem).at[jnp.where(win, gi, 0)].add(
                        jnp.where(win, R(v, row["rd"]), 0).astype(storage)))
                return v, jnp.where(order >= 0, contrib, mem), s

            def op_vfma(v, mem, s):
                res = R(v, row["ra"]) * R(v, row["rb"]) + R(v, row["rd"])
                return W(v, row["rd"], qdyn(res, row["sewi"]), act), mem, s

            def op_vfma_vs(v, mem, s):
                res = s[row["sd"]] * R(v, row["rb"]) + R(v, row["rd"])
                return W(v, row["rd"], qdyn(res, row["sewi"]), act), mem, s

            def op_vfadd(v, mem, s):
                res = R(v, row["ra"]) + R(v, row["rb"])
                return W(v, row["rd"], qdyn(res, row["sewi"]), act), mem, s

            def op_vfmul(v, mem, s):
                res = R(v, row["ra"]) * R(v, row["rb"])
                return W(v, row["rd"], qdyn(res, row["sewi"]), act), mem, s

            def op_vfwmul(v, mem, s):
                res = R(v, row["ra"]) * R(v, row["rb"])
                return W(v, row["rd"], qdyn(res, row["wsewi"]), act), mem, s

            def op_vfwma(v, mem, s):
                res = R(v, row["ra"]) * R(v, row["rb"]) + R(v, row["rd"])
                return W(v, row["rd"], qdyn(res, row["wsewi"]), act), mem, s

            def op_vfncvt(v, mem, s):
                return (W(v, row["rd"], qdyn(R(v, row["ra"]),
                                             row["sewi"]), act), mem, s)

            def int_op(kind, sticky):
                # integer/fixed-point branch: int32 view in, wrapped or
                # saturated result out; vxsat is part of the carried scan
                # state (the scalar file), so the cached-trace contract
                # is untouched — saturation is data, not structure
                def op(v, mem, s):
                    a = to_int(R(v, row["ra"]))
                    b = to_int(R(v, row["rb"]))
                    res, sat = jax.lax.switch(
                        row["sewi"],
                        [lambda x, y, w=w: int_arith(kind, x, y, w)
                         for w in isa.SEWS], a, b)
                    v = W(v, row["rd"], res.astype(storage), act)
                    if sticky:
                        flag = allmax(jnp.max(
                            jnp.where(act & sat, 1, 0)))
                        s = s.at[isa.VXSAT_SREG].max(flag.astype(storage))
                    return v, mem, s
                return op

            def op_vins(v, mem, s):
                vals = jnp.broadcast_to(s[row["sd"]], (window,))
                return W(v, row["rd"], qdyn(vals, row["sewi"])), mem, s

            def op_vext(v, mem, s):
                hit = mask & (ids == row["aux"])
                val = allsum(jnp.sum(jnp.where(hit, R(v, row["ra"]), 0)))
                return v, mem, s.at[row["sd"]].set(val)

            def op_vslide(v, mem, s):
                # SLDU: materialize the group globally (psum over lanes'
                # disjoint contributions — exact), then gather i+amount.
                # Tail-undisturbed (Ara2/RVV 1.0): body elements whose
                # source would come from at-or-past vl are NOT written —
                # they keep their old values, like every tail element
                src = jnp.where(mask, R(v, row["ra"]), 0)
                vec = allsum(jnp.zeros((gwin,), storage).at[
                    jnp.where(mask, ids, gwin)].set(src, mode="drop"))
                tgt = jnp.clip(ids + row["aux"], 0, gwin - 1)
                return (W(v, row["rd"], vec[tgt],
                          mask & (ids + row["aux"] < vl)), mem, s)

            def op_ldscalar(v, mem, s):
                return v, mem, s.at[row["sd"]].set(mem[row["imm"]])

            def cmp_op(kind):
                # mask-generating compares: exact 0/1 in mask layout,
                # mask-undisturbed where the compare is itself masked
                def op(v, mem, s):
                    if kind in ("vmfeq", "vmflt"):
                        a, b = R(v, row["ra"]), R(v, row["rb"])
                    else:
                        a = to_int(R(v, row["ra"]))
                        b = to_int(R(v, row["rb"]))
                    res = {"vmseq": lambda: a == b,
                           "vmsne": lambda: a != b,
                           "vmslt": lambda: a < b,
                           "vmsle": lambda: a <= b,
                           "vmfeq": lambda: a == b,
                           "vmflt": lambda: a < b}[kind]()
                    return W(v, row["rd"], res.astype(storage), act), mem, s
                return op

            def logical_op(kind):
                def op(v, mem, s):
                    a = R(v, row["ra"]) != 0    # activeness view
                    b = R(v, row["rb"]) != 0
                    res = {"vmand": a & b, "vmor": a | b,
                           "vmxor": a ^ b}[kind]
                    return W(v, row["rd"], res.astype(storage)), mem, s
                return op

            def op_vmerge(v, mem, s):
                sel = R(v, isa.MASK_REG) != 0
                vals = jnp.where(sel, R(v, row["ra"]), R(v, row["rb"]))
                return W(v, row["rd"], vals), mem, s

            def red_op(kind, wide=False):
                # classless tree reduction: materialize the ACTIVE body
                # globally (disjoint scatters + psum, exact), pad to the
                # static pow2 window with the op identity, fold halves.
                # The fold is identity-invariant to the pow2 padding, so
                # the oracle's next_pow2(vl) tree lands bit-identically.
                def op(v, mem, s):
                    if kind == "vredmax":
                        ident = MAX_IDENT[row["sewi"]]
                    elif kind == "vredmin":
                        ident = MIN_IDENT[row["sewi"]]
                    else:
                        ident = jnp.zeros((), storage)
                    tgt = jnp.where(act, ids, RED_P)
                    vec = allsum(jnp.zeros((RED_P,), storage).at[tgt].set(
                        R(v, row["ra"]), mode="drop"))
                    cnt = allsum(jnp.zeros((RED_P,), jnp.int32).at[tgt].set(
                        1, mode="drop"))
                    vec = jnp.where(cnt > 0, vec, ident)
                    n = RED_P
                    while n > 1:
                        n //= 2
                        lo, hi = vec[:n], vec[n:2 * n]
                        if kind == "vredmax":
                            vec = jnp.maximum(lo, hi)
                        elif kind == "vredmin":
                            vec = jnp.minimum(lo, hi)
                        else:
                            vec = lo + hi
                    res = qdyn(vec[0], row["wsewi"] if wide
                               else row["sewi"])
                    # scalar destination: element 0 only, nothing at vl=0
                    ok = (ids == 0) & (vl > 0)
                    return (W(v, row["rd"],
                              jnp.broadcast_to(res, (window,)), ok),
                            mem, s)
                return op

            named = {k: int_op(*v) for k, v in INT_OPS.items()}
            branches = [op_nop, op_vld, op_vlds, op_vgather, op_vlseg,
                        op_vst, op_vsseg, op_vsuxei, op_vfma, op_vfma_vs,
                        op_vfadd, op_vfmul, op_vfwmul, op_vfwma,
                        op_vfncvt, named["vadd"], op_vins, op_vext,
                        op_vslide, op_ldscalar, named["vsub"],
                        named["vmul"], named["vsaddu"], named["vsadd"],
                        named["vssub"], named["vsmul"],
                        cmp_op("vmseq"), cmp_op("vmsne"), cmp_op("vmslt"),
                        cmp_op("vmsle"), cmp_op("vmfeq"), cmp_op("vmflt"),
                        logical_op("vmand"), logical_op("vmor"),
                        logical_op("vmxor"), op_vmerge,
                        red_op("vredsum"), red_op("vredmax"),
                        red_op("vredmin"), red_op("vfwredsum", wide=True)]
            assert len(branches) == len(OPS)
            return jax.lax.switch(row["op"], branches, v, mem, s), None

        v0 = jnp.zeros((nregs, slots), storage)
        (_, mem, s), _ = jax.lax.scan(step, (v0, mem, s), rows)
        return mem, s

    if sig.batch == 1:
        # unbatched fast path: lax.switch executes ONE branch per step at
        # runtime (vmap would select over all of them even for batch 1)
        def batched(mems, svecs, sizes, rows):
            mem, s = one_program(mems[0], svecs[0], sizes[0],
                                 {k: a[0] for k, a in rows.items()})
            return mem[None], s[None]
    else:
        batched = jax.vmap(one_program)
    if mesh is None:
        return jax.jit(batched, donate_argnums=(0, 1))
    from jax.sharding import PartitionSpec as PS
    # one shard_map over every mesh axis (flat "lanes" or the nested
    # clusters × lanes pair): memory/scalars replicated, reconciled in
    # the step via the allsum/allmax folds above
    sharded = _shard_map(batched, mesh=mesh,
                         in_specs=(PS(), PS(), PS(), PS()),
                         out_specs=(PS(), PS()), check_vma=False)
    return jax.jit(sharded, donate_argnums=(0, 1))
