"""Vmapped CRUSH placement kernel (JAX) — bulk straw2 rule evaluation.

The reference computes placements one input at a time (crush_do_rule,
/root/reference/src/crush/mapper.c:900) and scales by threading
(ParallelPGMapper, /root/reference/src/osd/OSDMapMapping.h:18) or forked
batches (CrushTester.h:361).  On TPU the natural shape is data-parallel:
flatten the map into dense arrays, express one input's rule evaluation with
`lax.while_loop`/unrolled replica steps, and `vmap` over millions of inputs
in a single dispatch — hash, fixed-point log, and argmax are all int lane
ops.

Scope (the modern hot path): straw2 buckets, rules of the form
TAKE / CHOOSE(LEAF)_FIRSTN / CHOOSE(LEAF)_INDEP / SET_*_TRIES / EMIT, modern
tunables (choose_local_tries=0, local_fallback=0; descend_once, vary_r,
stable as set on the map).  Legacy bucket algs, local-retry tunables, and
chained choose steps stay on the exact host mapper (ceph_tpu.crush.mapper),
which this kernel is tested to match placement-for-placement (and the host
mapper is itself oracle-tested against the reference's compiled mapper.c).
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import List

import jax
import jax.numpy as jnp
import numpy as np

from ceph_tpu.crush import ln_table
from ceph_tpu.crush.map import (
    CRUSH_BUCKET_STRAW2, CRUSH_RULE_CHOOSELEAF_FIRSTN,
    CRUSH_RULE_CHOOSELEAF_INDEP, CRUSH_RULE_CHOOSE_FIRSTN,
    CRUSH_RULE_CHOOSE_INDEP, CRUSH_RULE_EMIT,
    CRUSH_RULE_SET_CHOOSELEAF_TRIES, CRUSH_RULE_SET_CHOOSE_TRIES,
    CRUSH_RULE_TAKE, CrushMap,
)
from ceph_tpu.ops import rjenkins

# straw2 draws are int64 fixed-point: the map arrays are built, and
# every rule program traced and dispatched, under a SCOPED
# jax.enable_x64(True) — never process-wide, where it would turn the
# Pallas kernels' index maps into i64 that Mosaic refuses to lower
S64_MIN = -(2**63)
# inputs per dispatch: the vmapped while-loops' temporaries grow with
# the batch (the whole 2^20-input batch needed most of a v5e's HBM)
MAX_BATCH = 1 << 16
UNDEF = jnp.int32(-0x7FFFFFFF)
NONE = jnp.int32(-0x80000000)


@dataclass
class DenseMap:
    """CrushMap flattened to device arrays; bucket row = -1 - bucket_id."""

    items: jnp.ndarray      # (NB, MS) int32, padded with 0
    weights: jnp.ndarray    # (NB, MS) int64 16.16, padded with 0
    sizes: jnp.ndarray      # (NB,) int32
    types: jnp.ndarray      # (NB,) int32
    dev_weight: jnp.ndarray  # (max_devices,) int64 16.16 in/out vector
    max_devices: int
    max_depth: int

    @classmethod
    def from_crush_map(cls, cmap: CrushMap,
                       weight: List[int] | None = None) -> "DenseMap":
        nb = max(-bid for bid in cmap.buckets)
        ms = max((b.size for b in cmap.buckets.values()), default=1) or 1
        items = np.zeros((nb, ms), dtype=np.int32)
        weights = np.zeros((nb, ms), dtype=np.int64)
        sizes = np.zeros(nb, dtype=np.int32)
        types = np.zeros(nb, dtype=np.int32)
        for bid, b in cmap.buckets.items():
            if b.alg != CRUSH_BUCKET_STRAW2:
                raise NotImplementedError(
                    "TPU kernel supports straw2 buckets; use the host mapper")
            row = -1 - bid
            items[row, : b.size] = b.items
            weights[row, : b.size] = b.weights
            sizes[row] = b.size
            types[row] = b.type
        depth = {}

        def bucket_depth(bid: int) -> int:
            if bid in depth:
                return depth[bid]
            b = cmap.buckets[bid]
            d = 1 + max((bucket_depth(i) for i in b.items if i < 0), default=0)
            depth[bid] = d
            return d

        max_depth = max((bucket_depth(b) for b in cmap.buckets), default=1)
        w = weight if weight is not None else cmap.full_weight_vector()
        with jax.enable_x64(True):
            return cls(items=jnp.asarray(items),
                       weights=jnp.asarray(weights),
                       sizes=jnp.asarray(sizes), types=jnp.asarray(types),
                       dev_weight=jnp.asarray(np.asarray(w, dtype=np.int64)),
                       max_devices=cmap.max_devices, max_depth=max_depth)


def crush_ln_jax(u):
    """Vectorized crush_ln (int64 in/out); u in [0, 0xffff]."""
    x = u.astype(jnp.int64) + 1
    bl = 32 - jax.lax.clz(x.astype(jnp.int32)).astype(jnp.int64)
    shift = jnp.where((x & 0x18000) != 0, 0, 16 - bl)
    x = x << shift
    iexpon = 15 - shift
    index1 = (x >> 8) << 1
    rh = jnp.asarray(ln_table.RH_LH_TBL)[index1 - 256]
    lh = jnp.asarray(ln_table.RH_LH_TBL)[index1 + 1 - 256]
    xl64 = ((x.astype(jnp.uint64) * rh.astype(jnp.uint64))
            >> jnp.uint64(48)).astype(jnp.int64)
    index2 = xl64 & 0xFF
    lh = lh + jnp.asarray(ln_table.LL_TBL)[index2]
    return (iexpon << 44) + (lh >> 4)


def _straw2_row(dm: DenseMap, row, x, r):
    """Choose one item from bucket row by straw2 argmax (first max wins)."""
    ids = dm.items[row]
    ws = dm.weights[row]
    ms = ids.shape[0]
    mask = jnp.arange(ms) < dm.sizes[row]
    u = rjenkins.hash32_3(x.astype(jnp.uint32), ids.astype(jnp.uint32),
                          jnp.uint32(r & 0xFFFFFFFF), xp=jnp)
    u = (u & jnp.uint32(0xFFFF)).astype(jnp.int64)
    ln = crush_ln_jax(u) - jnp.int64(0x1000000000000)
    draws = jnp.where(mask & (ws > 0), -((-ln) // jnp.maximum(ws, 1)),
                      jnp.int64(S64_MIN))
    return ids[jnp.argmax(draws)]


def _descend(dm: DenseMap, start_item, x, r, target_type):
    """Walk from start_item down to an item of target_type.

    Returns (item, empty_bad, type_bad):
    - empty_bad: hit an empty bucket (the reference rejects and retries);
    - type_bad: dead-ended on a wrong type / invalid id (the reference
      gives up on the replica: skip_rep in firstn, NONE in indep).
    """

    def step(carry):
        item, empty, depth = carry
        row = jnp.clip(-1 - item, 0, dm.sizes.shape[0] - 1)
        is_empty = dm.sizes[row] == 0
        nxt = _straw2_row(dm, row, x, r)
        item2 = jnp.where(is_empty, item, nxt)
        return item2, empty | is_empty, depth + 1

    def cond(carry):
        item, empty, depth = carry
        row = jnp.clip(-1 - item, 0, dm.sizes.shape[0] - 1)
        is_bucket = item < 0
        at_type = jnp.where(is_bucket, dm.types[row] == target_type,
                            target_type == 0)
        return (~empty) & is_bucket & (~at_type) & (depth < dm.max_depth + 1)

    item, empty_bad, _ = jax.lax.while_loop(
        cond, step, (start_item, jnp.bool_(False), jnp.int32(0)))
    row = jnp.clip(-1 - item, 0, dm.sizes.shape[0] - 1)
    ok_type = jnp.where(item < 0, dm.types[row] == target_type,
                        target_type == 0)
    type_bad = (~empty_bad) & (~ok_type | (item >= dm.max_devices))
    return item, empty_bad, type_bad


def _is_out(dm: DenseMap, item, x):
    """Weight-vector rejection (mapper.c is_out)."""
    idx = jnp.clip(item, 0, dm.dev_weight.shape[0] - 1)
    w = dm.dev_weight[idx]
    u = (rjenkins.hash32_2(x.astype(jnp.uint32), item.astype(jnp.uint32),
                           xp=jnp) & jnp.uint32(0xFFFF)).astype(jnp.int64)
    out_of_range = item >= dm.dev_weight.shape[0]
    return out_of_range | (w == 0) | ((w < 0x10000) & (u >= w))


def _leaf_choose(dm: DenseMap, domain, x, rep_base, parent_r, r_stride,
                 leaf_tries, out2, collide_limit):
    """The chooseleaf recursion: pick one device under `domain`.

    firstn: r' = rep_base + parent_r + ftotal' (stride 1), collisions checked
    against out2[:collide_limit] (mapper.c:573-591).
    indep:  r' = rep_base + parent_r + numrep*ftotal' (stride numrep), no
    collision check (mapper.c:785-796).
    Returns (leaf, failed).
    """

    def body(carry):
        ftotal, leaf, done = carry
        r = rep_base + parent_r + r_stride * ftotal
        cand, empty_bad, type_bad = _descend(dm, domain, x, r, jnp.int32(0))
        collide = jnp.any((jnp.arange(out2.shape[0]) < collide_limit)
                          & (out2 == cand))
        rejected = empty_bad | type_bad | collide | _is_out(dm, cand, x)
        leaf2 = jnp.where(rejected, leaf, cand)
        return ftotal + 1, leaf2, done | ~rejected

    def cond(carry):
        ftotal, _, done = carry
        return (~done) & (ftotal < leaf_tries)

    _, leaf, done = jax.lax.while_loop(
        cond, body, (jnp.int32(0), NONE, jnp.bool_(False)))
    return leaf, ~done


def _choose_firstn_jax(dm: DenseMap, root, x, numrep, target_type, tries,
                       leaf_tries, recurse_to_leaf, vary_r, stable,
                       result_max):
    out = jnp.full((result_max,), NONE, dtype=jnp.int32)
    out2 = jnp.full((result_max,), NONE, dtype=jnp.int32)
    outpos = jnp.int32(0)
    # status codes inside the retry loop: 0 trying, 1 placed, 2 skip_rep
    for rep in range(numrep):

        def body(carry, rep=rep):
            ftotal, item, leaf, status = carry
            r = jnp.int32(rep) + ftotal
            cand, empty_bad, type_bad = _descend(dm, root, x, r, target_type)
            collide = jnp.any((jnp.arange(result_max) < outpos)
                              & (out == cand))
            sub_r = jnp.where(vary_r > 0, r >> jnp.maximum(vary_r - 1, 0),
                              jnp.int32(0))
            rep_base = jnp.where(stable > 0, jnp.int32(0), outpos)
            lf, lfail = _leaf_choose(dm, cand, x, rep_base, sub_r,
                                     jnp.int32(1), leaf_tries, out2, outpos)
            leaf_reject = recurse_to_leaf & lfail
            dev_reject = (target_type == 0) & _is_out(dm, cand, x)
            reject = empty_bad | collide | leaf_reject | dev_reject
            placed = (~type_bad) & (~reject)
            status2 = jnp.where(type_bad, jnp.int32(2),
                                jnp.where(placed, jnp.int32(1), jnp.int32(0)))
            item2 = jnp.where(placed, cand, item)
            leaf2 = jnp.where(placed, lf, leaf)
            return ftotal + 1, item2, leaf2, status2

        def cond(carry):
            ftotal, _, _, status = carry
            return (status == 0) & (ftotal < tries)

        _, item, leaf, status = jax.lax.while_loop(
            cond, body, (jnp.int32(0), NONE, NONE, jnp.int32(0)))
        placed = status == 1
        out = out.at[outpos].set(jnp.where(placed, item, out[outpos]))
        out2 = out2.at[outpos].set(jnp.where(placed, leaf, out2[outpos]))
        outpos = outpos + placed.astype(jnp.int32)
    result = jnp.where(recurse_to_leaf, out2, out)
    return result, outpos


def _choose_indep_jax(dm: DenseMap, root, x, left0, numrep, target_type,
                      tries, leaf_tries, recurse_to_leaf, result_max):
    """left0 = clamped output count; numrep = unclamped arg for r-mixing."""
    out = jnp.full((result_max,), NONE, dtype=jnp.int32)
    out2 = jnp.full((result_max,), NONE, dtype=jnp.int32)
    out = out.at[:left0].set(UNDEF)
    out2 = out2.at[:left0].set(UNDEF)
    n = jnp.int32(numrep)

    def round_body(carry):
        ftotal, out, out2, left = carry

        def rep_step(rep, state):
            out, out2, left = state
            undef = out[rep] == UNDEF
            r = rep + n * ftotal
            cand, empty_bad, type_bad = _descend(dm, root, x, r, target_type)
            collide = jnp.any(out[:left0] == cand)
            leaf, lfail = _leaf_choose(dm, cand, x, rep, r, n, leaf_tries,
                                       out2, jnp.int32(0))
            leaf_fail = recurse_to_leaf & lfail
            dev_out = (target_type == 0) & _is_out(dm, cand, x)
            # type_bad permanently assigns NONE; other rejects leave UNDEF
            make_none = undef & type_bad
            place = undef & ~type_bad & ~empty_bad & ~collide & ~leaf_fail \
                & ~dev_out
            newval = jnp.where(place, cand,
                               jnp.where(make_none, NONE, out[rep]))
            out = out.at[rep].set(newval)
            new2 = jnp.where(place & recurse_to_leaf, leaf,
                             jnp.where(make_none, NONE, out2[rep]))
            out2 = out2.at[rep].set(new2)
            left = left - (place | make_none).astype(jnp.int32)
            return out, out2, left

        out, out2, left = jax.lax.fori_loop(0, left0, rep_step,
                                            (out, out2, left))
        return ftotal + 1, out, out2, left

    def round_cond(carry):
        ftotal, _, _, left = carry
        return (left > 0) & (ftotal < tries)

    _, out, out2, _ = jax.lax.while_loop(
        round_cond, round_body, (jnp.int32(0), out, out2, jnp.int32(left0)))
    out = jnp.where(out == UNDEF, NONE, out)
    out2 = jnp.where(out2 == UNDEF, NONE, out2)
    result = jnp.where(recurse_to_leaf, out2, out)
    return result, jnp.int32(left0)


def compile_rule(cmap: CrushMap, ruleno: int, result_max: int,
                 weight: List[int] | None = None):
    """Build a jitted bulk evaluator for one rule: xs (N,) -> (N, result_max).

    Unplaced firstn slots hold CRUSH_ITEM_NONE at the tail; indep holds NONE
    in place, mirroring crush_do_rule's output contract.  Inputs past
    MAX_BATCH run as padded dispatches of one compiled shape.

    `run.trace_one` embeds the single-input evaluator in a caller's own
    trace, which must then run under ``jax.enable_x64(True)``.
    """
    dm = DenseMap.from_crush_map(cmap, weight)
    rule = cmap.rules[ruleno]
    if cmap.choose_local_tries or cmap.choose_local_fallback_tries:
        raise NotImplementedError("legacy local tries: use the host mapper")
    n_chooses = sum(1 for s in rule.steps
                    if s.op in (CRUSH_RULE_CHOOSE_FIRSTN,
                                CRUSH_RULE_CHOOSE_INDEP,
                                CRUSH_RULE_CHOOSELEAF_FIRSTN,
                                CRUSH_RULE_CHOOSELEAF_INDEP))
    takes = sum(1 for s in rule.steps if s.op == CRUSH_RULE_TAKE)
    if n_chooses != takes:
        raise NotImplementedError(
            "chained choose steps: use the host mapper")

    def one(x):
        if not jax.config.jax_enable_x64:
            raise RuntimeError(
                "CRUSH rule traced without jax.enable_x64(True): the "
                "int64 straw2 draws would silently truncate")
        x = x.astype(jnp.int32)
        choose_tries = cmap.choose_total_tries + 1
        choose_leaf_tries = 0
        w_item = None
        results = []
        emitted = 0
        for step in rule.steps:
            if step.op == CRUSH_RULE_TAKE:
                w_item = jnp.int32(step.arg1)
            elif step.op == CRUSH_RULE_SET_CHOOSE_TRIES:
                if step.arg1 > 0:
                    choose_tries = step.arg1
            elif step.op == CRUSH_RULE_SET_CHOOSELEAF_TRIES:
                if step.arg1 > 0:
                    choose_leaf_tries = step.arg1
            elif step.op in (CRUSH_RULE_CHOOSE_FIRSTN,
                             CRUSH_RULE_CHOOSELEAF_FIRSTN,
                             CRUSH_RULE_CHOOSE_INDEP,
                             CRUSH_RULE_CHOOSELEAF_INDEP):
                assert w_item is not None, "rule has no TAKE before CHOOSE"
                firstn = step.op in (CRUSH_RULE_CHOOSE_FIRSTN,
                                     CRUSH_RULE_CHOOSELEAF_FIRSTN)
                recurse = step.op in (CRUSH_RULE_CHOOSELEAF_FIRSTN,
                                      CRUSH_RULE_CHOOSELEAF_INDEP)
                numrep = step.arg1 if step.arg1 > 0 else (
                    step.arg1 + result_max)
                if firstn:
                    if choose_leaf_tries:
                        leaf_tries = choose_leaf_tries
                    elif cmap.chooseleaf_descend_once:
                        leaf_tries = 1
                    else:
                        leaf_tries = choose_tries
                    res, cnt = _choose_firstn_jax(
                        dm, w_item, x, min(numrep, result_max - emitted),
                        jnp.int32(step.arg2), jnp.int32(choose_tries),
                        jnp.int32(leaf_tries), jnp.bool_(recurse),
                        jnp.int32(cmap.chooseleaf_vary_r),
                        jnp.int32(cmap.chooseleaf_stable), result_max)
                else:
                    leaf_tries = choose_leaf_tries if choose_leaf_tries else 1
                    res, cnt = _choose_indep_jax(
                        dm, w_item, x, min(numrep, result_max - emitted),
                        numrep, jnp.int32(step.arg2),
                        jnp.int32(choose_tries), jnp.int32(leaf_tries),
                        jnp.bool_(recurse), result_max)
                results.append((res, cnt))
                emitted += min(numrep, result_max - emitted)
                w_item = None
            elif step.op == CRUSH_RULE_EMIT:
                pass
        if not results:
            return jnp.full((result_max,), NONE, dtype=jnp.int32)
        if len(results) == 1:
            return results[0][0]
        return jnp.concatenate([r for r, _ in results])[:result_max]

    batched = jax.jit(jax.vmap(one))

    def run(xs) -> np.ndarray:
        xs = np.asarray(xs, dtype=np.int32)
        with jax.enable_x64(True):
            if len(xs) <= MAX_BATCH:
                return np.asarray(batched(xs))
            out = np.empty((len(xs), result_max), dtype=np.int32)
            for lo in range(0, len(xs), MAX_BATCH):
                part = xs[lo:lo + MAX_BATCH]
                n = len(part)
                if n < MAX_BATCH:
                    part = np.pad(part, (0, MAX_BATCH - n))
                out[lo:lo + n] = np.asarray(batched(part))[:n]
            return out

    run.dense_map = dm
    run.trace_one = one  # traceable single-x evaluator for shard_map/pjit use
    run.result_max = result_max
    return run
