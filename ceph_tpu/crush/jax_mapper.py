"""Batched CRUSH mapping in JAX — millions of PGs per launch.

The reference maps PGs one at a time through scalar C
(`crush_do_rule` in `src/crush/mapper.c`; `osdmaptool --test-map-pgs`
loops it single-threaded — SURVEY.md §4.5).  Here the PG batch is the
vector axis: every straw2 draw becomes a [B, S] hash + argmax, retry
loops become masked `lax.while_loop`s bounded by `choose_total_tries`,
and the hierarchy walk is a fixed-depth masked descent.  Output is
bit-identical to the scalar oracle (`ceph_tpu.crush.mapper`), enforced by
tests/test_crush_jax.py.

Supported: ALL bucket algorithms (straw2, uniform, straw, list,
tree), rules of one or more `take → [set_*] → choose-chain → emit`
blocks including multi-step choose chains and hybrid multi-block
rules, all chooseleaf vary_r/stable tunable combinations, choose_args
weight-sets, and reweights.  Falls back to the oracle (loudly, via
the CLI tools) only for: choose_local(_fallback)_tries > 0,
chooseleaf mid-chain, and indep inside a multi-step chain.

Requires jax_enable_x64 (straw2 draws are 64-bit fixed point).
"""

from __future__ import annotations

import dataclasses
import functools
import hashlib
import os

import numpy as np

from .hash import crush_hash32_2, crush_hash32_3, crush_hash32_4
from .ln import crush_ln
from .map import CRUSH_ITEM_NONE, CrushMap, Rule

_NONE = CRUSH_ITEM_NONE
_I64_MIN = -(1 << 63)

# Incremented whenever a mapper PROGRAM is built (traced) — i.e. NOT on
# a warm start from the on-disk export cache and NOT on
# set_weights/remap.  Tests assert on deltas to prove that weight-only
# changes and cache hits never retrace.
TRACE_COUNT = 0

# order of the runtime weight-table tuple `run` takes (every table a
# reweight/balancer round can change — the compiled program's ONLY
# value-dependence on bucket weights)
_WTAB_FIELDS = ("w", "wm_m", "wm_s", "wm_a", "hids", "strawsc",
                "lsums", "tnodes")


@functools.lru_cache(maxsize=1)
def _ln16_s_tbl() -> np.ndarray:
    """The straw2 numerator for every possible 16-bit hash: the whole
    `(crush_ln(u) - 2^48) << 16` chain (floor-log2, two coarse/fine
    table gathers, a u64 multiply) collapses into ONE 64 Ki-entry i64
    gather per item — u only has 65536 values.  Values wrap mod 2^64
    exactly as the scalar oracle's shift does."""
    u = np.arange(0x10000, dtype=np.uint64)
    lnv = crush_ln(u).astype(np.int64) - np.int64(1 << 48)
    return (lnv.astype(np.uint64) << np.uint64(16)).astype(np.int64)


@functools.lru_cache(maxsize=None)
def _magicu64(d: int) -> tuple[int, int, int]:
    """Granlund–Montgomery magic for exact unsigned 64-bit division by
    the constant d (Hacker's Delight magicu): n // d ==
    (mulhi(n, M) >> s) when add == 0, else
    (((n - t) >> 1) + t) >> (s - 1) with t = mulhi(n, M).

    TPUs have no 64-bit integer divide (XLA emulates it with a long
    shift-subtract loop); each weight's magic triple is computed on the
    host and rides into the program as a runtime argument alongside the
    weight table, so each item's divisor becomes ~4 32-bit multiplies —
    and a reweight only re-derives the triples, never the program.
    """
    if d <= 0:
        return 0, 0, 0
    nc = ((1 << 64) // d) * d - 1
    for p in range(64, 129):
        # smallest p with 2^p > nc*(d - 1 - (2^p - 1) % d) gives an
        # exact magic for all n ≤ nc (covers the full u64 range)
        if (1 << p) > nc * (d - 1 - (((1 << p) - 1) % d)):
            m = ((1 << p) + d - 1 - (((1 << p) - 1) % d)) // d
            return m & ((1 << 64) - 1), p - 64, int(m >> 64)
    raise AssertionError(f"no magic for {d}")


@functools.lru_cache(maxsize=1)
def _ln_limb_tables() -> tuple[np.ndarray, np.ndarray]:
    """RH/LH and LL tables split into exact 8-bit limbs for the
    one-hot MXU lookup path: [129, 14] (RH limbs 0-6, LH limbs 7-13 —
    RH[0] and LH[128] are exactly 2^48, so bit 48 needs a 7th limb)
    and [256, 6] (LL limbs).  8-bit limbs so BOTH dot operands are
    bf16 (0..255 and 0/1 are exact in bf16; a one-hot row selects
    exactly one limb per output, and the f32 accumulation of a single
    product is exact) — an f32 limb table makes XLA materialize the
    one-hot upcast to f32, doubling the dominant HBM traffic."""
    from .ln import RH_LH_TBL, LL_TBL
    rh = RH_LH_TBL[0::2].astype(np.uint64)       # [129]
    lh = RH_LH_TBL[1::2].astype(np.uint64)
    rhlh = np.zeros((129, 14), dtype=np.float32)
    for i in range(7):
        # 7 8-bit limbs cover bit 48 (RH[0] and LH[128] are 2^48)
        rhlh[:, i] = ((rh >> np.uint64(8 * i)) &
                      np.uint64(0xFF)).astype(np.float32)
        rhlh[:, 7 + i] = ((lh >> np.uint64(8 * i)) &
                          np.uint64(0xFF)).astype(np.float32)
    ll = np.zeros((256, 6), dtype=np.float32)
    for i in range(6):
        ll[:, i] = ((LL_TBL.astype(np.uint64) >> np.uint64(8 * i)) &
                    np.uint64(0xFF)).astype(np.float32)
    return rhlh, ll


# PGs per launch: every straw2 draw spans (chunk x widest bucket)
# elements at ~350 B of device temporaries each (measured with
# `compiled.memory_analysis()` for v5e: a 16-wide hierarchy at 65536
# PGs holds 338 MB, a flat 1024-OSD bucket 22.5 GB — over the 16 GB
# HBM).  2^22 elements keeps one launch near 1.5 GB.
_MAX_CHUNK = 1 << 16
_LAUNCH_ELEMS = 1 << 22


def _launch_chunk(widest: int) -> int:
    chunk = _MAX_CHUNK
    while chunk > 256 and chunk * max(widest, 1) > _LAUNCH_ELEMS:
        chunk //= 2
    return chunk


def _compact_idx(mask, size: int, fill: int):
    """``jnp.nonzero(mask, size=size, fill_value=fill)[0]`` with an
    int32 prefix sum.  Under x64 jnp.nonzero counts in int64, and the
    TPU compiler's emulated 64-bit cumsum (a u32-pair reduce-window)
    overflows its VMEM stack."""
    import jax.numpy as jnp
    pos = jnp.cumsum(mask.astype(jnp.int32)) - np.int32(1)
    tgt = jnp.where(mask & (pos < size), pos, np.int32(size))
    return jnp.full((size,), fill, jnp.int32).at[tgt].set(
        jnp.arange(mask.shape[0], dtype=jnp.int32), mode="drop")


def _onehot_rows(idx, n: int):
    """[..] int32 -> [.., n] bf16 one-hot (0/1 are exact in bf16; the
    dot promotes to f32)."""
    import jax.numpy as jnp
    return (idx[..., None] == jnp.arange(n, dtype=jnp.int32)
            ).astype(jnp.bfloat16)


def _straw2_numerator_onehot(u):
    """Device crush_ln: the straw2 numerator ((crush_ln(u) - 2^48)
    << 16) computed with small one-hot MXU table lookups instead of a
    64Ki-entry gather.

    Rationale (measured on v5e in round 5): ANY HBM gather on this backend
    costs ~135 ms per [128Ki, 64] lookup regardless of table size —
    it was the entire CRUSH device cost — while one-hot matmuls and
    u64 limb arithmetic are ~10-100x cheaper.  Bit-exact vs
    `_ln16_s_tbl` over all 65536 inputs (tests/test_crush_jax.py).

    u: [..] any uint/int dtype holding 16-bit hash values.
    """
    import jax
    import jax.numpy as jnp
    rhlh_np, ll_np = _ln_limb_tables()
    rhlh = jnp.asarray(rhlh_np)
    ll3 = jnp.asarray(ll_np)

    x32 = (u.astype(jnp.uint32) & np.uint32(0xFFFF)) + np.uint32(1)
    # floor_log2 via the f32 exponent field (exact: x <= 2^16 < 2^24)
    f = x32.astype(jnp.float32)
    expo = (jax.lax.bitcast_convert_type(f, jnp.int32)
            >> 23) - np.int32(127)
    bits = jnp.maximum(np.int32(0), np.int32(15) - expo)
    xs = (x32 << bits.astype(jnp.uint32))     # normalized [2^15, 2^16]
    iexpon = (np.int32(15) - bits).astype(jnp.uint32)

    k = (xs >> np.uint32(8)).astype(jnp.int32) - np.int32(128)  # [0,128]
    lead = u.shape
    oh1 = _onehot_rows(k.reshape(-1), 129)                 # [N, 129]
    limbs14 = jax.lax.dot_general(
        oh1, rhlh.astype(jnp.bfloat16), (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)                # [N, 14]
    limbs14 = limbs14.reshape(*lead, 14)

    # Everything below runs in u32 pairs — XLA's emulated u64 vector
    # ops measured ~14 ms of the 21.7 ms numerator at [128Ki, 64];
    # the pair arithmetic needs ~1/3 of that.  Bounds are proven in
    # comments and checked exhaustively (all 65536 inputs) in tests.
    u32 = jnp.uint32

    def l32(i):
        return limbs14[..., i].astype(u32)

    # rh as (lo32, hi17): only the pieces xl64 needs
    rl0 = l32(0) | (l32(1) << u32(8))                 # rh bits 0-15
    rl1 = l32(2) | (l32(3) << u32(8))                 # rh bits 16-31
    rh_hi = l32(4) | (l32(5) << u32(8)) | (l32(6) << u32(16))
    # xl64 = (xs * rh) >> 48 with xs <= 2^16, rh <= 2^48:
    #   xs*rl_i < 2^32 (u32-exact); mid = (P0>>16)+P1 <= 2^32-1;
    #   H = xs*rh_hi < 2^32 (rh_hi = 2^16 only at k=0 where
    #   xs < 2^15+2^8, and xs = 2^16 only at k=128 where rh_hi = 2^15)
    p0 = xs * rl0
    mid = (p0 >> u32(16)) + xs * rl1
    h = xs * rh_hi
    w = (h & u32(0xFFFF)) << u32(16)
    sum_ = w + mid
    carry = (sum_ < w).astype(u32)
    idx2 = (((h >> u32(16)) + carry) & u32(0xFF)).astype(jnp.int32)

    oh2 = _onehot_rows(idx2.reshape(-1), 256)              # [N, 256]
    limbs6 = jax.lax.dot_general(
        oh2, ll3.astype(jnp.bfloat16), (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)                # [N, 6]
    limbs6 = limbs6.reshape(*lead, 6)

    # (lh + llv) as a u32 pair: add the 8-bit limbs in f32 first
    # (t_i <= 510, exact), then assemble with explicit carries
    t = [limbs14[..., 7 + i] + (limbs6[..., i] if i < 6 else 0.0)
         for i in range(7)]
    t = [x.astype(u32) for x in t]
    lo_part = t[0] + (t[1] << u32(8)) + (t[2] << u32(16))   # < 2^26
    s_lo32 = lo_part + ((t[3] & u32(0xFF)) << u32(24))
    # the add above CAN wrap (max ~2^25.7 + 255*2^24 > 2^32): detect
    # the carry the unsigned way and feed it into the high word
    c_lo = (s_lo32 < lo_part).astype(u32)
    s_hi32 = ((t[3] >> u32(8)) + t[4] + (t[5] << u32(8))
              + (t[6] << u32(16)) + c_lo)                   # < 2^26
    # result = (iexpon << 44) + ((lh+ll) >> 4), then s = result << 16
    # (the - 2^48 vanishes: 2^48 << 16 == 0 mod 2^64)
    r_lo = (s_lo32 >> u32(4)) | (s_hi32 << u32(28))
    r_hi = (s_hi32 >> u32(4)) + (iexpon << u32(12))
    out_hi = (r_hi << u32(16)) | (r_lo >> u32(16))
    out_lo = r_lo << u32(16)
    s = ((out_hi.astype(jnp.uint64) << np.uint64(32))
         | out_lo.astype(jnp.uint64))
    return jax.lax.bitcast_convert_type(s, jnp.int64)


def _mulhi_u64(a, b):
    """High 64 bits of a*b via 32-bit limbs (exact in uint64)."""
    import jax.numpy as jnp
    mask = np.uint64(0xFFFFFFFF)
    a0, a1 = a & mask, a >> np.uint64(32)
    b0, b1 = b & mask, b >> np.uint64(32)
    lo_lo = a0 * b0
    hi_lo = a1 * b0
    lo_hi = a0 * b1
    cross = (lo_lo >> np.uint64(32)) + (hi_lo & mask) + (lo_hi & mask)
    return (a1 * b1 + (hi_lo >> np.uint64(32)) + (lo_hi >> np.uint64(32))
            + (cross >> np.uint64(32)))


def _straw2_draws(u, w, wmagic=None, any_add=True, ln16=None):
    """Per-item draws: u [.., S] hashes (0..0xffff), w [.., S] int64 weights.

    Returns int64 draws; w==0 ⇒ INT64_MIN (never wins except at index 0
    of an all-zero bucket, matching the reference's `i == 0` seed).

    wmagic: optional (M, s, add) uint64/int32 arrays matching w, from
    `_magicu64` — the division-free path for host-derived weight tables.
    any_add: False only when the caller KNOWS the magic table can never
    contain add-case entries; weight tables passed as runtime arguments
    must keep the add branch (the values are not visible at trace time).
    ln16: the _ln16_s_tbl array, passed as a traced argument so the
    512 KiB table is a program parameter, not an inline HLO literal
    (inlining it tripled compile time).
    """
    import jax
    import jax.numpy as jnp
    # draw = (ln << 16) / w.  Numerator source:
    #   - "onehot" (the TPU path): computed on device via small
    #     one-hot MXU lookups — HBM gathers cost ~135 ms per
    #     [128Ki, 64] call on this backend regardless of table size
    #     and were ~95% of the whole mapper's runtime;
    #   - otherwise one 64Ki-entry i64 gather (fast on CPU), from the
    #     passed-in table (a program parameter, not an HLO literal).
    if isinstance(ln16, str) and ln16 == "onehot":
        s = _straw2_numerator_onehot(u)
    else:
        tbl = jnp.asarray(_ln16_s_tbl()) if ln16 is None else ln16
        s = tbl[u.astype(jnp.int32)]
    neg = s < 0
    mag = jax.lax.bitcast_convert_type(jnp.abs(s), jnp.uint64)
    if wmagic is None:
        wq = jnp.maximum(w, np.int64(1)).astype(jnp.uint64)
        q = mag // wq
    else:
        M, sh, add = wmagic
        t = _mulhi_u64(mag, M)
        q = t >> sh.astype(jnp.uint64)
        if any_add:
            # add case evaluates q = ((n - t)/2 + t) >> (s - 1); the
            # only s == 0 add case is d == 1, where the quotient is n
            q_add = (((mag - t) >> np.uint64(1)) + t) >> (
                jnp.maximum(sh, 1).astype(jnp.uint64) - np.uint64(1))
            q_add = jnp.where(sh == 0, mag, q_add)
            q = jnp.where(add.astype(bool), q_add, q)
    qi = jax.lax.bitcast_convert_type(q, jnp.int64)
    draws = jnp.where(neg, -qi, qi)
    return jnp.where(w > 0, draws, np.int64(_I64_MIN))


class BatchMapper:
    """Compile one CRUSH rule into a batched x → device-vector function.

    __call__(xs[B], reweight[max_devices]?) → int32 [B, result_max];
    firstn results are compacted with CRUSH_ITEM_NONE padding at the end,
    indep results keep positional NONE holes (EC shard order).

    Compilation is SHAPE-keyed, not value-keyed: every weight-derived
    table is a runtime argument of the jitted program, so
    `set_weights(new_cmap)` / `remap({bucket_id: weights})` rebind a
    weight-only map change onto the already-compiled executable with
    zero retraces (asserted by tests/test_compile_cache.py).  The
    traced program is also `jax.export`ed to an on-disk cache
    (`native.aot.CompileCache`) so a fresh process with the same
    topology shape skips tracing too — `cache_hit` reports that.
    """

    def __init__(self, cmap: CrushMap, rule: Rule | int,
                 result_max: int | None = None,
                 chunk: int | None = None):
        import jax

        if not jax.config.jax_enable_x64:
            # straw2 draws are 64-bit fixed point.  Entry points
            # (CLIs, balancer, bench) opt in via utils.ensure_x64();
            # flipping the process-global flag from inside a library
            # constructor would silently change dtype semantics for
            # the whole embedding process
            raise RuntimeError(
                "BatchMapper needs 64-bit ints: call "
                "ceph_tpu.utils.ensure_x64() (or set JAX_ENABLE_X64=1)")
        if isinstance(rule, int):
            rule = cmap.rule_by_id(rule)
        self.cmap = cmap
        self.rule = rule
        self.chunk = chunk
        self._ln_mode = os.environ.get(
            "CEPH_TPU_CRUSH_LN",
            "onehot" if jax.default_backend() == "tpu" else "table")
        t = cmap.tunables

        # --- multi-block rules: take ... emit, take ... emit -------------
        # (reference crush_do_rule just keeps appending to `result`
        # across blocks; the classic use is hybrid placement — e.g.
        # primary on an SSD root, replicas on an HDD root.)  Each
        # block compiles as its own single-block mapper and the
        # outputs concatenate.  The reference's `numrep <= 0` rule is
        # `numrep += result_max` (crush_do_rule caps at EMIT, not at
        # choose), so a later block can draw more than the remaining
        # slots; but a non-final block that comes up SHORT shifts every
        # later block's positions, so those PGs re-map through the
        # scalar oracle (exactness over speed on that rare path).
        self._subs = None
        blocks = self._split_blocks(rule.steps)
        if len(blocks) > 1:
            self._init_multiblock(blocks, result_max)
            return

        # --- parse the rule: take + a CHAIN of choose steps + emit -------
        # (the reference rule VM, `crush_do_rule`: each choose step's
        # outputs become the next step's roots; set_* steps override
        # tunables for the steps that follow)
        take = None
        chain: list[dict] = []
        tries = t.choose_total_tries
        leaf_tries = 0
        vary_r = t.chooseleaf_vary_r
        stable = t.chooseleaf_stable
        local_tries = t.choose_local_tries
        local_fb = t.choose_local_fallback_tries
        emitted = False
        for s in rule.steps:
            if s.op == "take":
                if take is not None or emitted:
                    raise NotImplementedError(
                        "multiple take/emit blocks: use the scalar "
                        "oracle")
                take = s.arg1
            elif s.op == "set_choose_tries":
                tries = s.arg1 if s.arg1 > 0 else tries
            elif s.op == "set_chooseleaf_tries":
                leaf_tries = s.arg1 if s.arg1 > 0 else leaf_tries
            elif s.op == "set_chooseleaf_vary_r":
                vary_r = s.arg1 if s.arg1 >= 0 else vary_r
            elif s.op == "set_chooseleaf_stable":
                stable = s.arg1 if s.arg1 >= 0 else stable
            elif s.op == "set_choose_local_tries":
                local_tries = s.arg1 if s.arg1 >= 0 else local_tries
            elif s.op == "set_choose_local_fallback_tries":
                local_fb = s.arg1 if s.arg1 >= 0 else local_fb
            elif s.op in ("choose_firstn", "chooseleaf_firstn",
                          "choose_indep", "chooseleaf_indep"):
                chain.append({
                    "op": s.op, "numrep": s.arg1, "target": s.arg2,
                    "firstn": s.op.endswith("firstn"),
                    "leaf": s.op.startswith("chooseleaf"),
                    "tries": tries, "leaf_tries": leaf_tries,
                    "vary_r": vary_r, "stable": stable,
                })
            elif s.op == "emit":
                emitted = True
            else:
                raise NotImplementedError(f"rule step {s.op}: use the oracle")
        if take is None or not chain:
            raise ValueError("rule must contain take and a choose step")
        if local_tries or local_fb:
            raise NotImplementedError(
                "choose_local(_fallback)_tries: use the scalar oracle")
        if any(st["leaf"] for st in chain[:-1]):
            raise NotImplementedError(
                "chooseleaf mid-chain: use the scalar oracle")
        if len(chain) > 1 and not all(st["firstn"] for st in chain):
            raise NotImplementedError(
                "indep in a multi-step chain: use the scalar oracle")

        choose = chain[-1]
        self.firstn = choose["firstn"]
        self.recurse = choose["leaf"]
        self.target_type = choose["target"]
        numrep = choose["numrep"]
        if result_max is None:
            if numrep <= 0:
                raise ValueError("numrep<=0 rule needs explicit result_max")
            result_max = numrep
            for st in chain[:-1]:
                if st["numrep"] <= 0:
                    raise ValueError(
                        "numrep<=0 chain needs explicit result_max")
                result_max *= st["numrep"]
        if numrep <= 0:
            numrep += result_max
        self.numrep = min(numrep, result_max)
        self.result_max = result_max
        # resolved per-step reps + retry budgets
        for st in chain:
            n = st["numrep"]
            st["reps"] = n + result_max if n <= 0 else n
            if st["firstn"]:
                st["rtries"] = (st["leaf_tries"] if st["leaf_tries"]
                                else (1 if t.chooseleaf_descend_once
                                      else st["tries"]))
            else:
                st["rtries"] = (st["leaf_tries"] if st["leaf_tries"]
                                else 1)
        self.chain = chain
        self.tries = choose["tries"]
        self.recurse_tries = choose["rtries"]
        self.take = take

        # --- flatten the bucket table ------------------------------------
        # Split on the compile-cache contract: `_flatten_static` is
        # everything the compiled program bakes in (topology shapes,
        # algs, tree structure); `_set_weight_tables` is everything a
        # reweight/balancer round can change — those tables are
        # RUNTIME ARGUMENTS of the jitted function, so two maps with
        # equal static tables share one executable.
        self._install_static(self._flatten_static(cmap))
        if self.chunk is None:
            self.chunk = _launch_chunk(self._S)
        self._set_weight_tables(cmap)
        # descent depths + per-step size bounds: at BFS step t from
        # the possible roots only a statically-known set of buckets
        # can be under the cursor, so each straw2 scans that step's
        # max bucket size instead of the global max (the canonical
        # root→rack→host map has a size-1 top level that would
        # otherwise pay a full-S hash+argmax per element).  Chain
        # step i descends from step i-1's target-type buckets.
        prev_starts = [take]
        for st in chain:
            st["step_sizes"] = self._bfs_step_sizes(prev_starts,
                                                    st["target"])
            prev_starts = [b.id for b in cmap.buckets
                           if b is not None
                           and b.type == st["target"]]
        self.step_sizes1 = chain[-1]["step_sizes"]
        self.d1 = len(self.step_sizes1)
        if self.recurse:
            starts = [b.id for b in cmap.buckets
                      if b is not None and b.type == self.target_type]
            self.step_sizes2 = self._bfs_step_sizes(starts, 0)
            self.d2 = len(self.step_sizes2)
        else:
            self.step_sizes2 = []
            self.d2 = 0

        self._fn, self.cache_hit = self._compile()

    # -- static/dynamic table split ---------------------------------------

    def _flatten_static(self, cmap: CrushMap) -> dict:
        """Shape/topology tables — the compiled program's constants.

        supported algs: straw2 (the modern default), plus the legacy
        algs uniform/straw/list/tree, all vectorized.  uniform's
        permutation cache LOOKS call-order-stateful (the r=0 fast
        path), but the first Fisher-Yates step produces exactly the
        fast path's transposition, so bucket_perm_choose is a pure
        function of (bucket, x, r) — verified against the oracle
        over shuffled query orders (tests/test_crush_jax.py) — and
        the batched path recomputes the unfold per element."""
        nb = len(cmap.buckets)
        S = 1
        for b in cmap.buckets:
            if b is None:
                continue
            if b.alg not in ("straw2", "uniform", "straw", "list",
                             "tree"):
                raise NotImplementedError(
                    f"bucket alg {b.alg}: use the scalar oracle")
            if b.size == 0:
                raise ValueError("empty bucket in map")
            S = max(S, b.size)
        # choose_args (balancer weight-set): per-POSITION weight
        # overrides and id substitution (reference CrushWrapper
        # choose_args / bucket_straw2_choose's position argument).
        # The position COUNT is a table shape, hence static.
        P = 1
        for arg in cmap.choose_args.values():
            if arg.get("weight_set"):
                P = max(P, len(arg["weight_set"]))
        items = np.zeros((nb, S), dtype=np.int32)
        sizes = np.zeros(nb, dtype=np.int32)
        btype = np.zeros(nb, dtype=np.int32)
        alg_num = {"straw2": 0, "straw": 1, "list": 2, "tree": 3,
                   "uniform": 4}
        acode = np.zeros(nb, dtype=np.int32)
        bids = np.zeros(nb, dtype=np.int32)
        from .mapper import _tree_node_weights
        trees = {row: _tree_node_weights(b)[1]
                 for row, b in enumerate(cmap.buckets)
                 if b is not None and b.alg == "tree"}
        # tree node COUNT is a function of bucket size alone — the
        # node VALUES (weights) live in the runtime tables
        NT = max(trees.values(), default=2)
        troot = np.ones(nb, dtype=np.int32)
        tdepth = 0
        for row, b in enumerate(cmap.buckets):
            if b is None:
                continue
            items[row, :b.size] = b.items
            sizes[row] = b.size
            btype[row] = b.type
            acode[row] = alg_num[b.alg]
            bids[row] = b.id
            if b.alg == "tree":
                num = trees[row]
                troot[row] = num >> 1
                d = 0
                n = num >> 1
                while n and (n & 1) == 0:
                    d += 1
                    n >>= 1
                tdepth = max(tdepth, d)
        return {
            "nb": nb, "S": S, "P": P, "NT": NT,
            "items": items, "sizes": sizes, "btype": btype,
            "acode": acode, "bids": bids, "troot": troot,
            "tdepth": tdepth,
            "uniform_smax": max(
                (b.size for b in cmap.buckets
                 if b is not None and b.alg == "uniform"), default=0),
            "algs": sorted({b.alg for b in cmap.buckets
                            if b is not None}),
            "bucket_by_id": {b.id: b for b in cmap.buckets
                             if b is not None},
        }

    def _install_static(self, st: dict) -> None:
        self._items = st["items"]
        self._sizes, self._btype = st["sizes"], st["btype"]
        self._nb, self._S, self._P = st["nb"], st["S"], st["P"]
        self._NT = st["NT"]
        self._bucket_by_id = st["bucket_by_id"]
        self._uniform_smax = st["uniform_smax"]
        self._algs = st["algs"]
        self._acode, self._bids = st["acode"], st["bids"]
        self._troot, self._tdepth = st["troot"], st["tdepth"]

    def _set_weight_tables(self, cmap: CrushMap) -> None:
        """Weight-derived tables — runtime ARGUMENTS of the compiled
        program: the [P, nb, S] weight sets with their straw2 magic
        triples, choose_args hash-id substitutions, and the legacy-alg
        derivations (straw scalers, list prefix sums, tree node
        weights — the reference's crush_calc_straw /
        crush_make_tree_bucket).  Rebuilding these is the WHOLE cost
        of `set_weights`: no retrace, no XLA compile."""
        nb, S, P = self._nb, self._S, self._P
        hash_ids = np.zeros((nb, S), dtype=np.int32)
        weights = np.zeros((P, nb, S), dtype=np.int64)
        strawsc = np.zeros((nb, S), dtype=np.int64)
        lsums = np.zeros((nb, S), dtype=np.int64)
        tnodes = np.zeros((nb, self._NT), dtype=np.int64)
        from .mapper import _tree_node_weights, calc_straw_scalers
        for row, b in enumerate(cmap.buckets):
            if b is None:
                continue
            hash_ids[row, :b.size] = b.items
            arg = cmap.choose_args.get(b.id) or {}
            # choose_args act on straw2 buckets only (the oracle's
            # bucket_straw2_choose is the sole reader) — a weight-set
            # attached to a legacy bucket must not displace the plain
            # weights the legacy formulas read
            ws = (arg.get("weight_set")
                  if b.alg == "straw2" else None)
            if arg.get("ids") and b.alg == "straw2":
                hash_ids[row, :b.size] = arg["ids"]
            for p in range(P):
                if ws:
                    weights[p, row, :b.size] = ws[min(p, len(ws) - 1)]
                elif len(b.weights) == b.size:
                    weights[p, row, :b.size] = b.weights
                else:
                    # uniform buckets may carry only item_weight; the
                    # per-item weights only feed straw2 draws (masked
                    # out for uniform rows) and the summary APIs
                    weights[p, row, :b.size] = b.item_weight
            if b.alg == "straw":
                strawsc[row, :b.size] = calc_straw_scalers(b.weights)
            elif b.alg == "list":
                lsums[row, :b.size] = np.cumsum(b.weights)
            elif b.alg == "tree":
                nodes, num = _tree_node_weights(b)
                tnodes[row, :num] = nodes
        # division-free straw2: magic constants per DISTINCT weight
        # (TPU has no native u64 divide)
        mw = np.zeros((P, nb, S), dtype=np.uint64)
        sw = np.zeros((P, nb, S), dtype=np.int32)
        aw = np.zeros((P, nb, S), dtype=np.int32)
        for d in np.unique(weights):
            if d <= 0:
                continue
            msk = weights == d
            mw[msk], sw[msk], aw[msk] = _magicu64(int(d))
        self._weights, self._hash_ids = weights, hash_ids
        self._wmagic = (mw, sw, aw)
        self._strawsc, self._lsums = strawsc, lsums
        self._tnodes = tnodes
        self._wtab_dev = None   # device copies re-upload lazily

    def set_weights(self, cmap: CrushMap,
                    _check_rule: bool = True) -> "BatchMapper":
        """Rebind to `cmap`'s weights WITHOUT recompiling.

        Everything shape-like must be unchanged: topology (bucket ids,
        items, sizes, types, algs), rule steps, tunables, max_devices.
        Raises ValueError when the change is not weight-only — callers
        (e.g. ``OSDMap.batch_mapper``) catch that and build a fresh
        mapper.  On success: zero retraces, zero XLA compiles — only
        the host-side weight tables are rebuilt."""
        if _check_rule:
            try:
                rule = cmap.rule_by_id(self.rule.id)
            except Exception as e:
                raise ValueError(
                    f"rule {self.rule.id} missing from new map") from e
            if ([(s.op, s.arg1, s.arg2) for s in rule.steps]
                    != [(s.op, s.arg1, s.arg2)
                        for s in self.rule.steps]):
                raise ValueError("rule changed: rebuild the mapper")
        if cmap.tunables != self.cmap.tunables:
            raise ValueError("tunables changed: rebuild the mapper")
        if max(cmap.max_devices, 1) != max(self.cmap.max_devices, 1):
            raise ValueError("max_devices changed: rebuild the mapper")
        if self._subs is not None:
            # sub-mappers carry synthetic per-block rules derived from
            # the (just verified) original — skip their rule lookup
            for sub in self._subs:
                sub.set_weights(cmap, _check_rule=False)
            self.cmap = cmap
            return self
        st = self._flatten_static(cmap)
        same = (st["nb"] == self._nb and st["S"] == self._S
                and st["P"] == self._P and st["NT"] == self._NT
                and st["tdepth"] == self._tdepth
                and st["uniform_smax"] == self._uniform_smax
                and st["algs"] == self._algs
                and np.array_equal(st["items"], self._items)
                and np.array_equal(st["sizes"], self._sizes)
                and np.array_equal(st["btype"], self._btype)
                and np.array_equal(st["acode"], self._acode)
                and np.array_equal(st["troot"], self._troot))
        if not same:
            raise ValueError("topology changed: rebuild the mapper")
        self.cmap = cmap
        self._bucket_by_id = st["bucket_by_id"]
        self._set_weight_tables(cmap)
        return self

    def remap(self, new_weights) -> "BatchMapper":
        """Weight-only rebind reusing the compiled executable.

        `new_weights` is either a full CrushMap (must match this
        mapper's topology shape — see `set_weights`) or a
        ``{bucket_id: [per-item 16.16 weights]}`` dict patched onto
        the current map.  A dict patch changes ONLY the named buckets:
        CRUSH surfaces a child's total weight as the parent's item
        weight, so callers mirroring ``ceph osd crush reweight``
        should patch the ancestor buckets too (or pass the full
        recomputed CrushMap)."""
        if isinstance(new_weights, CrushMap):
            return self.set_weights(new_weights)
        by_id = dict(new_weights)
        buckets = []
        for b in self.cmap.buckets:
            if b is not None and b.id in by_id:
                ws = [int(w) for w in by_id.pop(b.id)]
                if len(ws) != b.size:
                    raise ValueError(
                        f"bucket {b.id}: {len(ws)} weights != "
                        f"size {b.size}")
                b = dataclasses.replace(
                    b, weights=ws,
                    item_weight=(ws[0] if b.alg == "uniform"
                                 else b.item_weight))
            buckets.append(b)
        if by_id:
            raise ValueError(f"unknown bucket ids {sorted(by_id)}")
        return self.set_weights(
            dataclasses.replace(self.cmap, buckets=buckets))

    # -- compile / warm start ---------------------------------------------

    def _wtab(self):
        """Device copies of the runtime weight tables (lazy: a
        set_weights drops them, the next call re-uploads once)."""
        if self._wtab_dev is None:
            import jax.numpy as jnp
            mw, sw, aw = self._wmagic
            self._wtab_dev = tuple(
                jnp.asarray(a) for a in (
                    self._weights, mw, sw, aw, self._hash_ids,
                    self._strawsc, self._lsums, self._tnodes))
        return self._wtab_dev

    def _arg_specs(self):
        import jax
        import jax.numpy as jnp
        sds = jax.ShapeDtypeStruct
        W = max(self.cmap.max_devices, 1)
        nb, S, P, NT = self._nb, self._S, self._P, self._NT
        wtab = (sds((P, nb, S), jnp.int64),
                sds((P, nb, S), jnp.uint64),
                sds((P, nb, S), jnp.int32),
                sds((P, nb, S), jnp.int32),
                sds((nb, S), jnp.int32),
                sds((nb, S), jnp.int64),
                sds((nb, S), jnp.int64),
                sds((nb, NT), jnp.int64))
        return (sds((self.chunk,), jnp.uint32),
                sds((W,), jnp.uint32),
                sds((0x10000,), jnp.int64),
                wtab)

    def _cache_key(self) -> dict:
        """The persistent-cache key: everything the compiled program
        depends on EXCEPT weight values — jax version, backend,
        shapes, topology arrays, rule steps, tunables.  Weight-only
        map changes therefore hash to the same entry.

        The CHUNK (xs batch length) is deliberately absent: results
        are chunk-invariant, so one exported program serves every
        requested chunk size — a warm start adopts the cached
        program's batch shape (see `_compile`) instead of re-tracing
        per chunk."""
        import jax

        def h(a):
            return hashlib.sha256(
                np.ascontiguousarray(a).tobytes()).hexdigest()[:16]

        return {
            # the exported program's name (entries without it: run)
            "program": "crush_map",
            "jax": jax.__version__,
            "backend": jax.default_backend(),
            "ln_mode": self._ln_mode,
            "numrep": self.numrep,
            "result_max": self.result_max,
            "max_devices": int(max(self.cmap.max_devices, 1)),
            "rule": [(s.op, s.arg1, s.arg2) for s in self.rule.steps],
            "tunables": dataclasses.asdict(self.cmap.tunables),
            "shape": {"nb": self._nb, "S": self._S, "P": self._P,
                      "NT": self._NT, "tdepth": self._tdepth,
                      "uniform_smax": self._uniform_smax,
                      "algs": self._algs},
            "topo": {n: h(getattr(self, "_" + n))
                     for n in ("items", "sizes", "btype", "acode",
                               "bids", "troot")},
            "steps1": self.step_sizes1,
            "steps2": self.step_sizes2,
        }

    def _compile(self):
        """Build or warm-start the jitted mapper → (fn, cache_hit).

        Warm start: the serialized `jax.export` module is deserialized
        from the on-disk cache — no tracing at all; XLA still compiles
        the module once per process (free on TPU when
        `utils.enable_compile_cache` has the persistent XLA cache on).
        Cold: trace once, export, persist; fall back to plain `jit`
        if this program can't export on this jax."""
        import jax
        global TRACE_COUNT
        from ..native.aot import CompileCache, named_call
        cache = CompileCache.default()
        if cache is not None:
            exported = cache.load_exported("crush", self._cache_key())
            if exported is not None:
                # the cache key is chunk-free: adopt the cached
                # program's batch shape as this mapper's chunk so any
                # requested chunk warm-starts from the one export
                # (callers chunk the xs stream at whatever granularity
                # the program bakes in — results are identical), as
                # long as one launch of it fits the device: a program
                # exported at a caller's larger chunk is rebuilt
                try:
                    cached = int(exported.in_avals[0].shape[0])
                except Exception:   # noqa: BLE001 — malformed export:
                    exported = None  # fall through to a cold build
                if exported is not None and \
                        cached > max(self.chunk, _launch_chunk(self._S)):
                    exported = None
                if exported is not None:
                    self.chunk = cached
                    return jax.jit(named_call("crush_map", exported)), True
        run = self._build()
        TRACE_COUNT += 1
        if cache is not None:
            try:
                from jax import export as jexport
                exported = jexport.export(jax.jit(run))(
                    *self._arg_specs())
                cache.store_exported("crush", self._cache_key(),
                                     exported)
                # execute through the exported module so cold and warm
                # processes feed XLA the identical program
                return jax.jit(named_call("crush_map", exported)), False
            except Exception:
                pass  # non-exportable on this jax — plain jit works
        return jax.jit(run), False

    def _bfs_step_sizes(self, start_items: list[int],
                        target_type: int) -> list[tuple[int, bool]]:
        """Per-descent-step (max bucket size, all-uniform?) from
        `start_items` until everything reachable is at `target_type`
        (or a device).  Length == the masked-descent trip count (old
        max_depth); `uniform` lets straw2 skip the per-row size mask."""
        steps = []
        frontier = set(start_items)
        for _ in range(len(self._bucket_by_id) + 1):
            nxt: set[int] = set()
            szs: set[int] = set()
            for it in frontier:
                if it < 0 and self.cmap.item_type(it) != target_type:
                    b = self._bucket_by_id.get(it)
                    if b is not None:
                        szs.add(b.size)
                        nxt.update(b.items)
            if not szs:
                break
            steps.append((max(szs), len(szs) == 1))
            frontier = nxt
        return steps

    # -- jitted pieces ----------------------------------------------------

    def _build(self):
        import jax
        import jax.numpy as jnp

        items = jnp.asarray(self._items)
        sizes = jnp.asarray(self._sizes)
        btype = jnp.asarray(self._btype)
        nb, S, P = self._nb, self._S, self._P

        def item_type(itm):
            rows = jnp.clip(-1 - itm, 0, nb - 1)
            return jnp.where(itm < 0, btype[rows], 0)

        legacy_algs = [a for a in self._algs if a != "straw2"]
        acode = jnp.asarray(self._acode)
        bids = jnp.asarray(self._bids)
        troot = jnp.asarray(self._troot)
        tdepth = self._tdepth
        # the 64Ki ln table rides in as an argument (set per call by
        # `run`); a box, not a closure constant, so the HLO carries a
        # parameter instead of a megabyte literal
        ln16_box = [None]
        # the weight tables (weights, straw2 magics, hash ids, straw
        # scalers, list sums, tree nodes) ride in the same way — they
        # are the ONLY value-dependence on bucket weights, which is
        # what lets set_weights/remap reuse the executable
        wt: dict = {}

        def _legacy_choose(rows, x, r, its, s_, u16):
            """Batched legacy algs (reference bucket_straw_choose /
            bucket_list_choose / bucket_tree_choose) — item per row;
            rows of other algs produce don't-care values that the
            caller masks out by alg code.  `u16` is straw2's already-
            computed [B, s_] 16-bit item hash (hash ids differ from
            items only on straw2 rows with choose_args ids, which are
            masked out of the legacy output anyway)."""
            barange = jnp.arange(rows.shape[0])
            outs = {}
            if "straw" in legacy_algs:
                draws = u16.astype(jnp.int64) * wt["strawsc"][:, :s_][rows]
                sel = jnp.argmax(draws, axis=1)
                outs[1] = its[barange, sel]
            if "uniform" in legacy_algs:
                # bucket_perm_choose: progressive Fisher-Yates keyed
                # by hash(x, bucket_id, step) — pure in (bucket, x, r)
                # (the r=0 fast path equals the first unfold step; see
                # the build-time comment).  Swaps via one-hot masks:
                # per-element dynamic indexing would hit this
                # backend's pathological gather path.
                size_u = sizes[rows].astype(jnp.uint32)   # [B]
                pr = (r.astype(jnp.uint32) % size_u).astype(jnp.int32)
                cols = jnp.arange(s_, dtype=jnp.int32)[None, :]
                perm = jnp.broadcast_to(cols,
                                        (rows.shape[0], s_))
                bid_u = bids[rows].astype(jnp.uint32)
                # perm[pr] is final after step pr (later steps only
                # touch positions > pr) and pr < size <= largest
                # uniform bucket — cap the unroll there
                for p in range(min(s_, self._uniform_smax)):
                    hp = crush_hash32_3(
                        x, bid_u, jnp.full_like(bid_u, p))
                    i = (hp % jnp.maximum(
                        size_u - np.uint32(p), np.uint32(1))
                         ).astype(jnp.int32)
                    swap = ((p <= pr) & (np.int32(p) <
                                         sizes[rows] - 1) & (i > 0))
                    j = np.int32(p) + i
                    ohj = (cols == j[:, None]) & swap[:, None]
                    colp = perm[:, p]
                    colj = jnp.sum(jnp.where(ohj, perm, 0), axis=1,
                                   dtype=jnp.int32)
                    val_p = jnp.where(swap, colj, colp)
                    perm = jnp.where(ohj, colp[:, None], perm)
                    perm = perm.at[:, p].set(val_p)
                ohpr = cols == pr[:, None]
                idx = jnp.sum(jnp.where(ohpr, perm, 0), axis=1,
                              dtype=jnp.int32)
                outs[4] = its[barange, idx]
            if "list" in legacy_algs:
                # newest→oldest walk; item i keeps the draw with
                # probability weight_i / prefixsum_i → the FIRST hit
                # from the high end, i.e. the max hit index
                h4 = crush_hash32_4(
                    x[:, None], its.astype(jnp.uint32),
                    r[:, None].astype(jnp.uint32),
                    bids[rows][:, None].astype(jnp.uint32))
                sums = wt["lsums"][:, :s_][rows]
                w = ((h4 & np.uint32(0xFFFF)).astype(jnp.int64)
                     * sums) >> np.int64(16)
                hit = (sums != 0) & (w < wt["w"][0, :, :s_][rows])
                rev = hit[:, ::-1]
                j = jnp.argmax(rev, axis=1)
                idx = jnp.where(hit.any(axis=1),
                                np.int32(s_ - 1) - j.astype(jnp.int32),
                                0)
                outs[2] = its[barange, idx]
            if "tree" in legacy_algs:
                n = troot[rows]
                nod = wt["tnodes"][rows]                 # [B, NT]
                for _ in range(tdepth):
                    even = (n & 1) == 0
                    wn = jnp.take_along_axis(
                        nod, n[:, None].astype(jnp.int32),
                        axis=1)[:, 0]
                    h = crush_hash32_4(
                        x, n.astype(jnp.uint32),
                        r.astype(jnp.uint32),
                        bids[rows].astype(jnp.uint32))
                    t_ = ((h.astype(jnp.uint64)
                           * wn.astype(jnp.uint64))
                          >> np.uint64(32)).astype(jnp.int64)
                    half = (n & -n) >> 1
                    left = n - half
                    wl = jnp.take_along_axis(
                        nod, left[:, None].astype(jnp.int32),
                        axis=1)[:, 0]
                    n2 = jnp.where(t_ < wl, left, n + half)
                    n = jnp.where(even, n2, n)
                # an all-zero subtree can land on a padding leaf;
                # clamp to a real item (rejected later by is_out)
                idx = jnp.minimum(n >> 1, sizes[rows] - 1)
                outs[3] = its[barange,
                              jnp.clip(idx, 0, s_ - 1)]
            return outs

        def straw2(rows, x, r, pos, step=None):
            """rows/x/r/pos [B] → chosen item [B].  `pos` is the output
            position selecting the choose_args weight-set column;
            `step` is this descent step's static (max size, uniform?)
            so the hash+argmax scans only the columns that can matter
            and skips the per-row size mask on uniform levels."""
            s_, uniform = (S, False) if step is None else step
            s_ = min(s_, S)
            its = items[:, :s_][rows]               # [B, s_]
            if s_ == 1:
                # a size-1 straw2 always selects its only item (the
                # reference's first loop iteration seeds the max)
                return its[:, 0]
            hids = wt["hids"][:, :s_][rows]
            if P == 1:
                # no choose_args positions: index the only weight set
                # statically instead of a clip+2-axis gather per row
                ws = wt["w"][0, :, :s_][rows]
                wm = (wt["wm_m"][0, :, :s_][rows],
                      wt["wm_s"][0, :, :s_][rows],
                      wt["wm_a"][0, :, :s_][rows])
            else:
                p = jnp.clip(pos, 0, P - 1)
                ws = wt["w"][:, :, :s_][p, rows]
                wm = (wt["wm_m"][:, :, :s_][p, rows],
                      wt["wm_s"][:, :, :s_][p, rows],
                      wt["wm_a"][:, :, :s_][p, rows])
            u = crush_hash32_3(x[:, None], hids.astype(jnp.uint32),
                               r[:, None].astype(jnp.uint32))
            u = (u & np.uint32(0xFFFF))
            # any_add stays on: the weight table is a runtime argument,
            # so trace time can't prove the add-case magics away
            draws = _straw2_draws(u, ws, wm, any_add=True,
                                  ln16=ln16_box[0])
            if not uniform:
                col = jnp.arange(s_, dtype=jnp.int32)
                draws = jnp.where(col[None, :] < sizes[rows][:, None],
                                  draws, np.int64(_I64_MIN))
            sel = jnp.argmax(draws, axis=1)
            out = its[jnp.arange(its.shape[0]), sel]
            if legacy_algs:
                ac = acode[rows]
                for code, val in _legacy_choose(rows, x, r, its,
                                                s_, u).items():
                    out = jnp.where(ac == np.int32(code), val, out)
            return out

        def descend(start, x, r, target, step_specs, pos,
                    indep_ft=None, indep_numrep=0):
            """Masked hierarchy walk until item type == target.

            indep paths recompute r PER LEVEL (reference
            crush_choose_indep: r = rep + parent_r + numrep*ftotal,
            except (numrep+1)*ftotal while inside a uniform bucket
            whose size is divisible by numrep) — pass the base r and the
            ftotal vector via `indep_ft` and the adjustment happens
            against each level's current bucket."""
            itm = start
            r_last = r
            for spec in (step_specs or [None]):
                isb = itm < 0
                rows = jnp.clip(-1 - itm, 0, nb - 1)
                t = jnp.where(isb, btype[rows], 0)
                need = isb & (t != target)
                if indep_ft is None:
                    rl = r
                else:
                    n_ = np.int32(indep_numrep)
                    udiv = ((acode[rows] == np.int32(4))
                            & (sizes[rows] % n_ == 0))
                    rl = r + jnp.where(udiv, n_ + 1, n_) * indep_ft
                    # the r in force where each row actually drew last
                    # — becomes the inner recursion's parent_r
                    r_last = jnp.where(need, rl, r_last)
                nxt = straw2(rows, x, rl, pos, spec)
                itm = jnp.where(need, nxt, itm)
            if indep_ft is not None:
                return itm, r_last
            return itm

        def dev_out(wdev, itm, x):
            """is_out() — reweight rejection for a device item."""
            w = wdev[jnp.clip(itm, 0, wdev.shape[0] - 1)]
            h = crush_hash32_2(x, itm.astype(jnp.uint32)) & np.uint32(0xFFFF)
            keep = (w >= np.uint32(0x10000)) | ((w > 0) & (h < w))
            return ~keep

        target = self.target_type
        numrep, tries = self.numrep, self.tries
        rtries = self.recurse_tries
        # chooseleaf with target type 0: the descent already lands on a
        # device; C takes the `out2[outpos] = item` direct path, so no
        # inner recursion happens
        leafmode = self.recurse and target != 0
        sizes1, sizes2 = self.step_sizes1, self.step_sizes2
        take = self.take
        chain = self.chain
        result_max = self.result_max
        vary_r = chain[-1]["vary_r"]

        def leaf_attempts(host, x, r, prev_leafs, wdev, pos, cfg,
                          rep0_leaf=None):
            """Inner chooseleaf: ≤ rtries attempts inside `host`.

            C: nested crush_choose_firstn(numrep=1 if stable else
            outpos+1, tries=rtries, parent_r=sub_r) — one leaf either
            way, but stable=0 offsets the inner r by the current
            outpos.  The recursive call keeps the OUTER outpos as the
            choose_args position.  `prev_leafs` is the leaf array so
            far (NONE-padded — NONE never equals a valid device).
            Returns (leaf, got)."""
            vr = cfg["vary_r"]
            sub_r = (r >> (vr - 1)) if vr else jnp.zeros_like(r)
            if rep0_leaf is not None:
                sub_r = sub_r + rep0_leaf
            got = jnp.zeros(r.shape, dtype=bool)
            dead = jnp.zeros(r.shape, dtype=bool)
            leaf = jnp.full(r.shape, _NONE, dtype=jnp.int32)
            for ft in range(cfg["rtries"]):
                ri = sub_r + np.int32(ft)
                cand = descend(host, x, ri, 0, sizes2, pos)
                valid = (cand >= 0) & (host < 0)
                collide = jnp.any(prev_leafs == cand[:, None], axis=1)
                reject = collide | dev_out(wdev, cand, x) | ~valid
                active = ~got & ~dead
                succ = active & ~reject
                leaf = jnp.where(succ, cand, leaf)
                got |= succ
                dead |= active & ~valid   # C: skip_rep — no more attempts
            return leaf, got

        def rep_while(x, roots, out, leafs, wdev, st0, rep_eff, cfg,
                      pos_vec=None):
            """The general retry loop for one firstn rep — shape-
            polymorphic (the straggler fallback runs it on a compacted
            slice) and root-vector-parameterized (chain steps descend
            from the previous step's picks)."""
            step_leaf = cfg["leaf"] and cfg["target"] != 0

            def body(st):
                ftotal, placed, dead, item, leaf = st
                active = ~placed & ~dead
                r = (rep_eff + ftotal).astype(jnp.int32)
                pos = (pos_vec if pos_vec is not None else
                       jnp.sum((out != _NONE).astype(jnp.int32),
                               axis=1))
                itm = descend(roots, x, r, cfg["target"],
                              cfg["step_sizes"], pos)
                valid = (item_type(itm) == cfg["target"]) & (roots < 0)
                collide = jnp.any(out == itm[:, None], axis=1)
                if step_leaf:
                    rep0_leaf = (None if cfg["stable"] else pos)
                    lf, lgot = leaf_attempts(itm, x, r, leafs, wdev,
                                             pos, cfg, rep0_leaf)
                    reject = collide | ~lgot
                else:
                    lf = itm
                    if cfg["target"] == 0:
                        reject = collide | dev_out(wdev, itm, x)
                    else:
                        reject = collide
                succ = active & valid & ~reject
                item = jnp.where(succ, itm, item)
                leaf = jnp.where(succ, lf, leaf)
                placed = placed | succ
                dead = dead | (active & ~valid)
                ftotal = ftotal + (active & valid & reject
                                   ).astype(jnp.int32)
                return ftotal, placed, dead, item, leaf

            def cond(st):
                ftotal, placed, dead, _, _ = st
                return jnp.any(~placed & ~dead
                               & (ftotal < cfg["tries"]))

            return jax.lax.while_loop(cond, body, st0)

        def firstn_chain_fn(x, wdev):
            """General firstn executor: any take→choose-chain→emit
            rule (the reference `crush_do_rule` accumulation), any
            stable/vary_r.  Each step appends into a fresh result
            buffer at a per-element outpos; the buffer feeds the next
            step as its root slots."""
            B = x.shape[0]
            barange = jnp.arange(B)
            roots = jnp.full((B, 1), take, dtype=jnp.int32)
            out = leafs = None
            for cfg in chain:
                slots = roots.shape[1]
                reps = min(cfg["reps"], result_max)
                cap = min(slots * reps, result_max)
                out = jnp.full((B, cap), _NONE, jnp.int32)
                leafs = jnp.full((B, cap), _NONE, jnp.int32)
                outpos = jnp.zeros((B,), jnp.int32)

                def root_body(carry, root, cfg=cfg, reps=reps,
                              cap=cap):
                    out, leafs, outpos = carry
                    entry = outpos      # outpos when this root starts

                    def rep_body(c, rep):
                        out, leafs, outpos = c
                        if cfg["stable"]:
                            rep_eff = jnp.full((B,), rep, jnp.int32)
                            rep_ok = jnp.ones((B,), bool)
                        else:
                            # C: rep starts at the entry outpos and
                            # must stay < numrep — later roots get
                            # fewer (or zero) reps
                            rep_eff = entry + rep
                            rep_ok = rep_eff < np.int32(cfg["reps"])
                        # C do_rule: `if wi >= 0 or (-1-wi) >= nb:
                        # continue` — NONE slots from an under-filled
                        # earlier step are negative but out of bucket
                        # range and must not descend
                        root_ok = (root < 0) & ((-1 - root) < nb)
                        active0 = rep_ok & root_ok \
                            & (outpos < np.int32(result_max))
                        st = (jnp.zeros((B,), jnp.int32),
                              ~active0,       # inactive = "placed"
                              jnp.zeros((B,), bool),
                              jnp.full((B,), _NONE, jnp.int32),
                              jnp.full((B,), _NONE, jnp.int32))
                        ftotal, placed, dead, item, leaf = rep_while(
                            x, root, out, leafs, wdev, st, rep_eff,
                            cfg, pos_vec=outpos)
                        succ = placed & active0 & (item != _NONE)
                        slot = jnp.minimum(outpos, np.int32(cap - 1))
                        out = out.at[barange, slot].set(
                            jnp.where(succ, item, out[barange, slot]))
                        leafs = leafs.at[barange, slot].set(
                            jnp.where(succ, leaf,
                                      leafs[barange, slot]))
                        outpos = outpos + succ.astype(jnp.int32)
                        return (out, leafs, outpos), None

                    (out, leafs, outpos), _ = jax.lax.scan(
                        rep_body, (out, leafs, outpos),
                        jnp.arange(reps, dtype=np.int32))
                    return (out, leafs, outpos), None

                (out, leafs, outpos), _ = jax.lax.scan(
                    root_body, (out, leafs, outpos),
                    jnp.moveaxis(roots, 0, 1))
                roots = out     # next step's root slots

            step_leaf = chain[-1]["leaf"] and chain[-1]["target"] != 0
            res = leafs if step_leaf else out
            if res.shape[1] < result_max:
                res = jnp.concatenate(
                    [res, jnp.full((B, result_max - res.shape[1]),
                                   np.int32(_NONE), jnp.int32)],
                    axis=1)
            return res[:, :result_max]

        # -- fast firstn: precomputed candidates + compacted stragglers
        #
        # The while-loop formulation above recomputes full-batch
        # descents every retry round: one colliding PG in a 128k batch
        # makes every PG pay another 2-3 straw2 rounds (the r4 10x
        # loss vs native scalar C).  With no choose_args (P == 1) a
        # descent depends only on (x, r), so the first R candidate
        # r-values are computed ONCE in a single batched launch and
        # rep selection becomes pure boolean logic; the rare PGs that
        # exhaust R candidates are compacted (~B/16) and finish in the
        # general loop at 1/16th the per-round cost.
        fast_R = numrep

        def firstn_fast_fn(x, wdev):
            B = x.shape[0]
            R = fast_R
            xt = jnp.tile(x, R)
            rt = jnp.repeat(jnp.arange(R, dtype=jnp.int32), B)
            zero = jnp.zeros((R * B,), jnp.int32)
            root = jnp.full((R * B,), take, dtype=jnp.int32)
            host_c = descend(root, xt, rt, target, sizes1, zero)
            valid_c = (item_type(host_c) == target).reshape(R, B)
            if leafmode:
                sub_r = ((rt >> (vary_r - 1)) if vary_r
                         else jnp.zeros_like(rt))
                leaf_fc, lval_fc, lok_fc = [], [], []
                for ft in range(rtries):
                    cand = descend(host_c, xt, sub_r + np.int32(ft),
                                   0, sizes2, zero)
                    lval = (cand >= 0) & (host_c < 0)
                    lok = lval & ~dev_out(wdev, cand, xt)
                    leaf_fc.append(cand.reshape(R, B))
                    lval_fc.append(lval.reshape(R, B))
                    lok_fc.append(lok.reshape(R, B))
            elif target == 0:
                devok_c = (~dev_out(wdev, host_c, xt)).reshape(R, B)
            host_c = host_c.reshape(R, B)
            barange = jnp.arange(B)

            def at_r(arr2d, rc):
                return arr2d[rc, barange]

            K = max(min(B, 256), B // 16)

            def rep_body(carry, rep):
                out, leafs = carry
                ftotal = jnp.zeros((B,), jnp.int32)
                placed = jnp.zeros((B,), bool)
                dead = jnp.zeros((B,), bool)
                item = jnp.full((B,), _NONE, jnp.int32)
                leaf = jnp.full((B,), _NONE, jnp.int32)
                # consume up to R precomputed candidates: each step a
                # PG inspects r = rep + ftotal (consecutive on reject)
                for _ in range(R):
                    r = rep + ftotal
                    in_range = r < R
                    rc = jnp.clip(r, 0, R - 1)
                    active = ~placed & ~dead & in_range
                    hc = at_r(host_c, rc)
                    valid = at_r(valid_c, rc)
                    collide = jnp.any(out == hc[:, None], axis=1)
                    if leafmode:
                        # inner ft selection against current leafs
                        lgot = jnp.zeros((B,), bool)
                        ldead = jnp.zeros((B,), bool)
                        lf = jnp.full((B,), _NONE, jnp.int32)
                        for ft in range(rtries):
                            lc_ = at_r(leaf_fc[ft], rc)
                            lv = at_r(lval_fc[ft], rc)
                            lo = at_r(lok_fc[ft], rc)
                            lcol = jnp.any(leafs == lc_[:, None],
                                           axis=1)
                            lact = ~lgot & ~ldead
                            lsucc = lact & lo & ~lcol
                            lf = jnp.where(lsucc, lc_, lf)
                            lgot |= lsucc
                            ldead |= lact & ~lv
                        reject = collide | ~lgot
                    else:
                        lf = hc
                        if target == 0:
                            reject = collide | ~at_r(devok_c, rc)
                        else:
                            reject = collide
                    succ = active & valid & ~reject
                    item = jnp.where(succ, hc, item)
                    leaf = jnp.where(succ, lf, leaf)
                    placed = placed | succ
                    dead = dead | (active & ~valid)
                    ftotal = ftotal + (active & valid & reject
                                       ).astype(jnp.int32)
                # stragglers: r >= R or still colliding — compact and
                # run the general loop on a K-slice until none remain
                def fb_cond(st):
                    ftotal, placed, dead, _, _ = st
                    return jnp.any(~placed & ~dead & (ftotal < tries))

                def fb_body(st):
                    ftotal, placed, dead, item, leaf = st
                    mask = ~placed & ~dead & (ftotal < tries)
                    idx = _compact_idx(mask, K, B)
                    ok = idx < B
                    idxc = jnp.minimum(idx, B - 1).astype(jnp.int32)
                    stk = (ftotal[idxc],
                           ~ok,            # pad rows: already "placed"
                           jnp.zeros((K,), bool),
                           jnp.full((K,), _NONE, jnp.int32),
                           jnp.full((K,), _NONE, jnp.int32))
                    rootk = jnp.full((K,), take, dtype=jnp.int32)
                    ftk, plk, ddk, itk, lfk = rep_while(
                        x[idxc], rootk, out[idxc], leafs[idxc], wdev,
                        stk, jnp.full((K,), rep, jnp.int32),
                        chain[-1])
                    # pad rows were marked placed with NONE items;
                    # mode="drop" discards their B sentinel index
                    ftotal = ftotal.at[idx].set(ftk, mode="drop")
                    placed = placed.at[idx].set(plk, mode="drop")
                    dead = dead.at[idx].set(ddk, mode="drop")
                    item = item.at[idx].set(itk, mode="drop")
                    leaf = leaf.at[idx].set(lfk, mode="drop")
                    return ftotal, placed, dead, item, leaf

                st = (ftotal, placed, dead, item, leaf)
                ftotal, placed, dead, item, leaf = jax.lax.while_loop(
                    fb_cond, fb_body, st)
                out = out.at[:, rep].set(
                    jnp.where(placed, item, np.int32(_NONE)))
                leafs = leafs.at[:, rep].set(
                    jnp.where(placed, leaf, np.int32(_NONE)))
                return (out, leafs), None

            init = (jnp.full((B, numrep), _NONE, jnp.int32),
                    jnp.full((B, numrep), _NONE, jnp.int32))
            (out, leafs), _ = jax.lax.scan(
                rep_body, init, jnp.arange(numrep, dtype=np.int32))
            res = leafs if leafmode else out
            order = jnp.argsort(res == _NONE, axis=1, stable=True)
            return jnp.take_along_axis(res, order, axis=1)

        UNDEF = np.int32(-0x7FFFFFFE)

        def _indep_leaf(host, x, parent_r, rep, wdev):
            """C: nested crush_choose_indep(left=1, numrep, outpos=rep,
            parent_r=r, tries=recurse_tries); the inner draw index is
            rep + parent_r + numrep*ftotal_inner — with the uniform-
            divisible (numrep+1) adjustment applied per level against
            the inner descent's own buckets."""
            got = jnp.zeros(parent_r.shape, dtype=bool)
            dead = jnp.zeros(parent_r.shape, dtype=bool)
            leaf = jnp.full(parent_r.shape, _NONE, dtype=jnp.int32)
            base = rep + parent_r
            for ft in range(rtries):
                cand, _ = descend(
                    host, x, base, 0, sizes2,
                    jnp.broadcast_to(rep, base.shape),
                    indep_ft=np.int32(ft), indep_numrep=numrep)
                valid = (cand >= 0) & (host < 0)
                reject = dev_out(wdev, cand, x) | ~valid
                active = ~got & ~dead
                succ = active & ~reject
                leaf = jnp.where(succ, cand, leaf)
                got |= succ
                dead |= active & ~valid
            return leaf, got

        def indep_rounds(x, wdev, out0, out20, ftotal0):
            """The general indep round loop.  (A candidate-precompute
            fast path with a compacted-straggler fallback calling this
            on a slice was built and measured at PARITY with the plain
            loop — each rep needs its own draw index, so candidates
            only relocate the same work — and was dropped; the
            extraction and state parameters remain from that
            evaluation and keep the loop independently testable.)"""
            B_ = x.shape[0]
            root = jnp.full((B_,), take, dtype=jnp.int32)

            def round_body(st):
                # one traced rep step under fori_loop (was numrep
                # unrolled copies — the r2 compile-time sink)
                out0_, out20_, ftotal = st

                def rep_step(rep, c):
                    out, out2 = c
                    needs = out[:, rep] == UNDEF
                    base = (rep.astype(jnp.int32)
                            * jnp.ones((B_,), jnp.int32))
                    itm, r_par = descend(
                        root, x, base, target, sizes1,
                        jnp.broadcast_to(rep, base.shape),
                        indep_ft=ftotal.astype(jnp.int32),
                        indep_numrep=numrep)
                    valid = item_type(itm) == target
                    collide = jnp.any(out == itm[:, None], axis=1)
                    if leafmode:
                        lf, lgot = _indep_leaf(itm, x, r_par, rep,
                                               wdev)
                        reject = collide | ~lgot
                    else:
                        lf = itm
                        if target == 0:
                            reject = collide | dev_out(wdev, itm, x)
                        else:
                            reject = collide
                    # invalid → permanent NONE (C: left--, slot dead)
                    kill = needs & ~valid
                    succ = needs & valid & ~reject
                    newv = jnp.where(succ, itm, jnp.where(
                        kill, np.int32(_NONE), out[:, rep]))
                    out = out.at[:, rep].set(newv)
                    newl = jnp.where(succ, lf, jnp.where(
                        kill, np.int32(_NONE), out2[:, rep]))
                    out2 = out2.at[:, rep].set(newl)
                    return out, out2

                out, out2 = jax.lax.fori_loop(0, numrep, rep_step,
                                              (out0_, out20_))
                return out, out2, ftotal + 1

            def round_cond(st):
                out, _, ftotal = st
                return (ftotal < tries) & jnp.any(out == UNDEF)

            st = (out0, out20, jnp.int32(ftotal0))
            out, out2, _ = jax.lax.while_loop(round_cond, round_body,
                                              st)
            return out, out2

        def indep_fn(x, wdev):
            B = x.shape[0]
            out0 = jnp.full((B, numrep), UNDEF, jnp.int32)
            out, out2 = indep_rounds(x, wdev, out0, out0, 0)
            res = out2 if leafmode else out
            return jnp.where(res == UNDEF, np.int32(_NONE), res)

        # fast path preconditions: single-step rule, no choose_args
        # positions (a descent must depend only on (x, r)), stable
        # rep indexing (stable=0 makes r data-dependent), and a small
        # inner-leaf retry budget (candidates are precomputed per ft)
        fast_ok = self.firstn and P == 1 and len(chain) == 1 \
            and chain[-1]["stable"] == 1 \
            and (not leafmode or rtries <= 4)
        if self.firstn:
            fn = firstn_fast_fn if fast_ok else firstn_chain_fn
        else:
            # indep keeps the general round loop: a candidate-precompute
            # variant was built and MEASURED at parity (each rep needs
            # its own draw index, so round-0 candidates just relocate
            # the same work) — not worth its compile cost
            fn = indep_fn

        def crush_map(x, wdev, ln16, wtab):
            # mode chosen at build: "onehot" computes the numerator on
            # device (TPU: gathers are the pathology); "table" uses
            # the passed-in 64Ki gather table (CPU: gathers are fine)
            ln16_box[0] = ("onehot" if self._ln_mode == "onehot"
                           else ln16)
            wt.update(zip(_WTAB_FIELDS, wtab))
            res = fn(x, wdev)
            if res.shape[1] < self.result_max:
                pad = jnp.full((x.shape[0], self.result_max - res.shape[1]),
                               np.int32(_NONE), jnp.int32)
                res = jnp.concatenate([res, pad], axis=1)
            return res

        return crush_map

    @staticmethod
    def _split_blocks(steps) -> list[list]:
        blocks: list[list] = []
        cur: list = []
        for s in steps:
            cur.append(s)
            if s.op == "emit":
                blocks.append(cur)
                cur = []
        if cur:
            blocks.append(cur)
        return blocks

    def _init_multiblock(self, blocks: list[list],
                         result_max: int | None) -> None:
        from .map import Rule as _Rule, Step as _Step
        for blk in blocks:
            ops = [s.op for s in blk]
            if not any(o.startswith("choose") for o in ops):
                raise NotImplementedError(
                    "multi-block rule with a chooseless block: use "
                    "the scalar oracle")
            if any(o.endswith("indep") for o in ops):
                raise NotImplementedError(
                    "indep in a multi-block rule: use the scalar "
                    "oracle")
        if result_max is None and any(
                s.arg1 <= 0 for blk in blocks for s in blk
                if s.op.startswith("choose")):
            raise ValueError(
                "numrep<=0 multi-block rule needs explicit result_max")
        # set_* steps persist across blocks in the reference VM —
        # carry the accumulated prefix into each later block
        carried: list = []
        sub_steps: list[list] = []
        for blk in blocks:
            sub_steps.append(list(carried) + list(blk))
            carried += [s for s in blk if s.op.startswith("set_")]
        subs = []
        prior = 0
        for i, st in enumerate(sub_steps):
            st2 = []
            for s in st:
                if s.op.startswith("choose") and s.arg1 <= 0:
                    # reference semantics: numrep += result_max (no
                    # osize term — crush_do_rule caps at EMIT, not at
                    # choose); the final cat[:, :result_max] trim
                    # reproduces the emit cap because firstn picks
                    # are prefix-stable in numrep
                    s = _Step(op=s.op,
                              arg1=s.arg1 + result_max,
                              arg2=s.arg2)
                    if s.arg1 <= 0:
                        raise ValueError(
                            "multi-block numrep resolves to <= 0")
                st2.append(s)
            sub = BatchMapper(
                self.cmap,
                _Rule(id=self.rule.id,
                      name=f"{self.rule.name}#block{i}",
                      steps=st2, type=self.rule.type),
                result_max=None, chunk=self.chunk)
            subs.append(sub)
            prior += sub.result_max
        self._subs = subs
        if self.chunk is None:
            self.chunk = min(sub.chunk for sub in subs)
        self.firstn = True
        self.cache_hit = all(sub.cache_hit for sub in subs)
        self.result_max = prior if result_max is None \
            else result_max

    def _call_multi(self, xs: np.ndarray, reweight) -> np.ndarray:
        outs = [sub(xs, reweight) for sub in self._subs]
        cat = np.concatenate(outs, axis=1)
        R = self.result_max
        if cat.shape[1] < R:
            cat = np.pad(cat, ((0, 0), (0, R - cat.shape[1])),
                         constant_values=_NONE)
        res = np.ascontiguousarray(cat[:, :R])
        # a NON-FINAL block that came up short shifts every later
        # block's position (and, for numrep<=0, its numrep) — those
        # PGs re-map through the scalar oracle, exactly
        short = np.zeros(len(xs), dtype=bool)
        for o in outs[:-1]:
            short |= (o == _NONE).any(axis=1)
        if short.any():
            from .mapper import do_rule
            w = (None if reweight is None else
                 [int(v) for v in np.asarray(reweight,
                                             dtype=np.uint32)])
            for i in np.nonzero(short)[0]:
                lst = do_rule(self.cmap, self.rule, int(xs[i]), R, w)
                row = np.full(R, _NONE, dtype=np.int32)
                row[:len(lst)] = lst[:R]
                res[i] = row
        return res

    def __call__(self, xs, reweight=None) -> np.ndarray:
        import jax.numpy as jnp
        xs = np.asarray(xs, dtype=np.uint32)
        if self._subs is not None:
            return self._call_multi(xs, reweight)
        W = max(self.cmap.max_devices, 1)
        if reweight is None:
            reweight = np.full(W, 0x10000, dtype=np.uint32)
        else:
            # normalize to the compiled [W] spec: the oracle's is_out
            # treats a device past the end of the vector as weight 0
            # (out), so shorter vectors zero-pad; entries past
            # max_devices can never be drawn, so longer vectors trim
            reweight = np.asarray(reweight, dtype=np.uint32)
            if len(reweight) < W:
                reweight = np.pad(reweight, (0, W - len(reweight)))
            elif len(reweight) > W:
                reweight = reweight[:W]
        wdev = jnp.asarray(reweight)
        wtab = self._wtab()
        ln16 = jnp.asarray(_ln16_s_tbl())
        # dispatch every chunk before fetching any result: jax's async
        # dispatch overlaps the per-call device latency across chunks
        # instead of serializing it
        from ..core.device_profiler import DeviceProfiler
        ln = DeviceProfiler.active().start(
            "crush_map", bytes_in=xs.nbytes + reweight.nbytes,
            rows=-(-len(xs) // self.chunk) * self.chunk
            if len(xs) else 0,
            rows_used=len(xs), cache_hit=self.cache_hit)
        pend = []
        try:
            for lo in range(0, len(xs), self.chunk):
                hi = min(lo + self.chunk, len(xs))
                part = xs[lo:hi]
                n = len(part)
                if n < self.chunk:
                    # ALWAYS pad to the chunk shape: one compiled program
                    # per mapper regardless of call sizes
                    part = np.pad(part, (0, self.chunk - n))
                pend.append((self._fn(jnp.asarray(part), wdev, ln16,
                                      wtab), n))
            out = np.concatenate(
                [np.asarray(res)[:n] for res, n in pend], axis=0)
        except Exception:
            if ln is not None:
                ln.abort()
            raise
        if ln is not None:
            ln.finish(bytes_out=out.nbytes)
        return out
