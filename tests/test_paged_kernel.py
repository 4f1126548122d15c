"""Differential harness for the fused Pallas paged-attention kernels.

Every test drives the same triangle of implementations over a shared paged
arena + block tables:

  fused   -- kernels.paged_attention (gather-free, block-table index map)
  gather  -- the serving reference path: arena[block_tables] materialized,
             then core.attention.{decode_attention_lamp, attention_lamp}
  dense   -- the same KV packed into a contiguous per-sequence cache (the
             PR-1 equivalence anchor)

and asserts outputs agree within float32 softmax roundoff and LAMP
selection counts match *exactly* (the two-pass kernel recomputes y_low with
dot_ps-identical rounding, so the look-ahead masks are bit-equal).

Coverage: (block_size, ragged lengths incl. block boundaries, window
offsets/starts, sliding windows, every LAMP rule + lamp-off), NaN-poisoned
dead blocks (fully-masked blocks must be skipped, not summed as zeros), a
hypothesis fuzz over random block tables / lengths (pinned "ci" profile),
and a seeded fallback walk that runs without hypothesis installed.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core.attention import (attention_lamp, attention_reference,
                                  decode_attention_lamp)
from repro.core.policy import LampSite
from repro.kernels import ops
from repro.kernels.paged_attention import (VMEM_BUDGET, decode_kv_bytes,
                                           grid_blocks, grid_step_vmem_bytes,
                                           kv_heads_per_step, supports_site)
from repro.models.layers import require_kernel_site

H, HKV, HD = 4, 2, 16

SITES = {
    "off": LampSite(enabled=False),
    "relaxed-g0": LampSite(enabled=True, rule="relaxed", mu=7, tau=0.05,
                           granularity=0),
    "relaxed-g1": LampSite(enabled=True, rule="relaxed", mu=7, tau=0.1,
                           granularity=1),
    "strict-g1": LampSite(enabled=True, rule="strict", mu=7, tau=0.1,
                          granularity=1),
    "ln-g0": LampSite(enabled=True, rule="relaxed_ln", mu=7, tau=0.2,
                      granularity=0, n_ref=64),
    "rule-none": LampSite(enabled=True, rule="none", mu=5, granularity=0),
}

TOL = dict(rtol=2e-5, atol=2e-6)


def _assert_counts_match(nsel, nsel_ref, site):
    """Selection counts are bit-exact for the max-based rules (relaxed /
    relaxed_ln / none / off): the kernel's y_low and running row max are
    bitwise identical to the reference. The strict rule additionally
    thresholds on the softmax normalizer l, which the kernel accumulates
    blockwise while the reference does one materialized sum -- a criterion
    value landing within an ulp of tau may flip, so strict gets a per-row
    slack of 1 (a real mask bug shifts counts by far more)."""
    nsel, nsel_ref = np.asarray(nsel), np.asarray(nsel_ref)
    if site.enabled and site.rule == "strict":
        np.testing.assert_allclose(nsel, nsel_ref, atol=1)
    else:
        np.testing.assert_array_equal(nsel, nsel_ref)


def _repeat_kv(t, n):
    return jnp.repeat(t, n, axis=1) if n > 1 else t


def _grid(x):
    """Snap random values to multiples of 2^-6 in [-8, 8]: every q.k product
    and every partial sum of one is then exact in float32, so the kernel's
    per-block dot and the reference's one batched dot give bitwise-equal
    logits whatever order XLA accumulates them in. Off the grid the two
    orders can differ in the last ulp, and the PS(mu) rounding of y_low
    turns a 1-ulp difference at a rounding midpoint into a 2^-mu one -- a
    flipped LAMP selection the exact-count assertions would misread as a
    kernel bug."""
    return np.clip(np.round(x * 64) / 64, -8, 8)


def make_paged(seed, lengths, bs, n_max, *, span=None, hkv=HKV):
    """Random head-major arena (n_blocks, Hkv, bs, hd) + per-row block
    tables. Row r owns ceil(span[r]/bs) distinct shuffled blocks (block 0
    stays the null block); the rest of the table is null-padded. span
    defaults to lengths (decode); prefill passes starts + window width."""
    rng = np.random.default_rng(seed)
    R = len(lengths)
    span = list(lengths) if span is None else list(span)
    n_blocks = 1 + R * n_max
    arena_k = jnp.asarray(_grid(rng.normal(size=(n_blocks, hkv, bs, HD)) * 1.5),
                          jnp.float32)
    arena_v = jnp.asarray(_grid(rng.normal(size=(n_blocks, hkv, bs, HD))),
                          jnp.float32)
    perm = rng.permutation(np.arange(1, n_blocks))
    bt = np.zeros((R, n_max), np.int32)
    for r in range(R):
        nb = -(-max(int(span[r]), 1) // bs)
        bt[r, :nb] = perm[r * n_max:r * n_max + nb]
    return arena_k, arena_v, jnp.asarray(bt)


def gathered_heads(arena_k, arena_v, bt, rep=H // HKV):
    """(R, H, n_max * bs, hd) per-row views: block walk concatenated along
    the position axis of each KV head, then repeated for GQA."""
    def view(arena):
        blocks = arena[bt]                          # (R, n_max, Hkv, bs, hd)
        flat = jnp.concatenate([blocks[:, j] for j in range(bt.shape[1])],
                               axis=2)              # (R, Hkv, n_max*bs, hd)
        return _repeat_kv(flat, rep)
    return view(arena_k), view(arena_v)


def check_decode(seed, lengths, bs, n_max, site, *, window=None,
                 check_dense=False, heads=(H, HKV)):
    """Fused decode vs gather (vs dense) on one random paged layout with
    `heads` = (query heads, KV heads)."""
    nh, hkv = heads
    arena_k, arena_v, bt = make_paged(seed, lengths, bs, n_max, hkv=hkv)
    lengths = jnp.asarray(lengths, jnp.int32)
    rng = np.random.default_rng(seed + 7)
    q = jnp.asarray(_grid(rng.normal(size=(len(lengths), nh, 1, HD)) * 1.5),
                    jnp.float32)

    out, nsel = ops.paged_decode_attention(q, arena_k, arena_v, bt, lengths,
                                           site, window=window)
    kh, vh = gathered_heads(arena_k, arena_v, bt, nh // hkv)
    want, aux = decode_attention_lamp(q, kh, vh, lengths, site,
                                      window=window, reduce=False)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), **TOL)
    _assert_counts_match(nsel, aux.n_selected, site)
    if check_dense:
        # pack the block walk into a contiguous dense cache: same values at
        # the same absolute positions -> same reference output
        dense_k = kh[:, :, :int(jnp.max(lengths))]
        dense_v = vh[:, :, :int(jnp.max(lengths))]
        want_d, _ = decode_attention_lamp(q, dense_k, dense_v, lengths, site,
                                          window=window, reduce=False)
        np.testing.assert_allclose(np.asarray(out), np.asarray(want_d), **TOL)
    return out, nsel


def check_prefill(seed, starts, bs, n_max, site, *, W=8, window=None,
                  block_q=None, heads=(H, HKV)):
    """Fused windowed prefill vs gather attention_lamp at offsets=starts,
    with `heads` = (query heads, KV heads)."""
    nh, hkv = heads
    starts = list(starts)
    span = [s + W for s in starts]
    arena_k, arena_v, bt = make_paged(seed, span, bs, n_max, span=span,
                                      hkv=hkv)
    st = jnp.asarray(starts, jnp.int32)
    rng = np.random.default_rng(seed + 13)
    q = jnp.asarray(_grid(rng.normal(size=(len(starts), nh, W, HD)) * 1.5),
                    jnp.float32)

    out, nsel = ops.paged_prefill_attention(q, arena_k, arena_v, bt, st, site,
                                            window=window, block_q=block_q)
    kh, vh = gathered_heads(arena_k, arena_v, bt, nh // hkv)
    if site.enabled:
        want, aux = attention_lamp(q, kh, vh, site, causal=True,
                                   window=window, offset=st, reduce=False)
        _assert_counts_match(nsel, aux.n_selected, site)
    else:
        want = attention_reference(q, kh, vh, causal=True, window=window,
                                   offset=st)
        np.testing.assert_array_equal(np.asarray(nsel), 0.0)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), **TOL)
    return out, nsel


# ------------------------------------------------------- differential grid

@pytest.mark.parametrize("site_name", sorted(SITES))
@pytest.mark.parametrize("bs,lengths", [
    (4, [3, 9, 16]),          # partial, mid-span, full span
    (8, [5, 16, 27]),         # partial block / exact boundary / ragged
])
def test_decode_differential_grid(bs, lengths, site_name):
    check_decode(0, lengths, bs, 4, SITES[site_name],
                 check_dense=site_name == "relaxed-g0")


@pytest.mark.parametrize("site_name", sorted(SITES))
@pytest.mark.parametrize("starts,block_q", [
    ([0, 5, 17], None),       # fresh prompt / mid-block / deep resume
    ([0, 8, 23], 4),          # boundary-aligned resume, tiled queries
])
def test_prefill_differential_grid(starts, block_q, site_name):
    check_prefill(1, starts, 8, 4, SITES[site_name], W=8, block_q=block_q)


@pytest.mark.parametrize("site_name", ["off", "relaxed-g0"])
def test_decode_sliding_window(site_name):
    check_decode(2, [5, 16, 27], 8, 4, SITES[site_name], window=12)


@pytest.mark.parametrize("site_name", ["off", "relaxed-g0"])
def test_prefill_sliding_window(site_name):
    check_prefill(3, [0, 9, 17], 8, 4, SITES[site_name], W=8, window=12,
                  block_q=4)


# ------------------------------------------------------------- head fold
# A grid step walks one KV block for hb KV heads, and hb follows the shape
# alone (kv_heads_per_step), so each case takes a shape whose rule gives the
# fold it covers: every head, an intermediate divisor, or one head.

PREFILL_FOLDS = {
    # name: ((H, Hkv), starts, bs, n_max, W, window, site, hb)
    "all-gqa": ((8, 4), [0, 5, 21], 8, 4, 8, None, "strict-g1", 4),
    "mid-window": ((4, 4), [0, 37], 16, 35, 512, 100, "relaxed-g0", 2),
    "one": ((2, 2), [3], 16, 65, 1024, None, "ln-g0", 1),
}

DECODE_FOLDS = {
    # name: ((H, Hkv), lengths, bs, n_max, window, site, hb)
    "all-gqa": ((8, 4), [5, 16, 27], 8, 4, None, "relaxed-g1", 4),
    "mid": ((143, 143), [3, 17, 30], 8, 4, None, "relaxed-g0", 13),
    "one-window": ((131, 131), [5, 16, 27], 8, 4, 12, "relaxed-g0", 1),
}


@pytest.mark.parametrize("case", sorted(PREFILL_FOLDS))
def test_prefill_head_fold(case):
    heads, starts, bs, n_max, W, window, site, hb = PREFILL_FOLDS[case]
    assert kv_heads_per_step(heads[1], heads[0] // heads[1], W, bs, HD) == hb
    check_prefill(11, starts, bs, n_max, SITES[site], W=W, window=window,
                  heads=heads)


@pytest.mark.parametrize("case", sorted(DECODE_FOLDS))
def test_decode_head_fold(case):
    heads, lengths, bs, n_max, window, site, hb = DECODE_FOLDS[case]
    assert kv_heads_per_step(heads[1], heads[0] // heads[1], 1, bs, HD) == hb
    check_decode(12, lengths, bs, n_max, SITES[site], window=window,
                 heads=heads)


@pytest.mark.parametrize("n_heads", [12, 25])
def test_kv_heads_per_step_rule(n_heads):
    """GPT-2's fold (one query head per KV head, 16-token blocks, 64-wide
    heads): every head at a decode tile; at every width a divisor of the
    heads within the VMEM budget, with no larger divisor fitting; and the
    widest prefill tile within the budget."""
    assert kv_heads_per_step(n_heads, 1, 1, 16, 64) == n_heads
    divisors = [d for d in range(1, n_heads + 1) if n_heads % d == 0]
    for wq in (1, 8, 16, 32, 64, 128, 256, 512, 1024):
        hb = kv_heads_per_step(n_heads, 1, wq, 16, 64)
        assert hb in divisors
        assert hb == 1 or grid_step_vmem_bytes(hb, 1, wq, 16, 64) \
            <= VMEM_BUDGET
        assert all(grid_step_vmem_bytes(d, 1, wq, 16, 64) > VMEM_BUDGET
                   for d in divisors if d > hb)
    hb = kv_heads_per_step(n_heads, 1, 1024, 16, 64)
    assert grid_step_vmem_bytes(hb, 1, 1024, 16, 64) <= VMEM_BUDGET


# --------------------------------------------------- mask/boundary corners

def test_decode_single_block_sequence():
    """A sequence living entirely inside one block (n_max-1 dead blocks)."""
    check_decode(4, [2, 1, 8], 8, 4, SITES["relaxed-g0"], check_dense=True)


def test_decode_length_on_block_boundary():
    check_decode(5, [8, 16, 32], 8, 4, SITES["relaxed-g0"], check_dense=True)


def test_decode_last_partial_block():
    check_decode(6, [9, 17, 31], 8, 4, SITES["strict-g1"], check_dense=True)


def test_decode_skips_fully_masked_trailing_block():
    """Dead table entries point at NaN-poisoned blocks: if the kernel read
    and 'summed them as zeros', 0 * NaN would poison the accumulator. The
    clamped index map + pl.when guard must keep the output clean."""
    bs, n_max = 8, 4
    lengths = [5, 16, 9]
    arena_k, arena_v, bt = make_paged(7, lengths, bs, n_max)
    # point every dead table slot at a real-but-poisoned block
    poison = arena_k.shape[0] - 1
    bt = np.asarray(bt).copy()
    for r, L in enumerate(lengths):
        bt[r, -(-L // bs):] = poison
    bt = jnp.asarray(bt)
    arena_k = arena_k.at[poison].set(jnp.nan)
    arena_v = arena_v.at[poison].set(jnp.nan)
    lengths = jnp.asarray(lengths, jnp.int32)
    rng = np.random.default_rng(8)
    q = jnp.asarray(rng.normal(size=(3, H, 1, HD)), jnp.float32)
    out, nsel = ops.paged_decode_attention(q, arena_k, arena_v, bt, lengths,
                                           SITES["relaxed-g0"])
    assert np.isfinite(np.asarray(out)).all()
    assert np.isfinite(np.asarray(nsel)).all()
    # and it still equals the clean gather reference over live blocks only
    clean_k = arena_k.at[poison].set(0.0)
    clean_v = arena_v.at[poison].set(0.0)
    kh, vh = gathered_heads(clean_k, clean_v, bt)
    want, _ = decode_attention_lamp(q, kh, vh, lengths, SITES["relaxed-g0"],
                                    reduce=False)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), **TOL)


def test_prefill_skips_blocks_above_causal_bound():
    """Blocks past a q-tile's causal horizon are dead for that tile; poison
    the final block and give every row a window that never reaches it."""
    bs, n_max, W = 8, 4, 8
    starts = [0, 4, 9]
    span = [s + W for s in starts]                  # spans end inside blk 0-2
    arena_k, arena_v, bt = make_paged(9, span, bs, n_max, span=span)
    poison = arena_k.shape[0] - 1
    bt = np.asarray(bt).copy()
    for r, s in enumerate(span):
        bt[r, -(-s // bs):] = poison                # dead tail entries
    bt = jnp.asarray(bt)
    arena_k = arena_k.at[poison].set(jnp.nan)
    arena_v = arena_v.at[poison].set(jnp.nan)
    st = jnp.asarray(starts, jnp.int32)
    rng = np.random.default_rng(10)
    q = jnp.asarray(rng.normal(size=(3, H, W, HD)), jnp.float32)
    out, nsel = ops.paged_prefill_attention(q, arena_k, arena_v, bt, st,
                                            SITES["relaxed-g0"], block_q=4)
    assert np.isfinite(np.asarray(out)).all()
    clean_k = arena_k.at[poison].set(0.0)
    clean_v = arena_v.at[poison].set(0.0)
    kh, vh = gathered_heads(clean_k, clean_v, bt)
    want, _ = attention_lamp(q, kh, vh, SITES["relaxed-g0"], causal=True,
                             offset=st, reduce=False)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), **TOL)


def test_supports_site_gate():
    assert supports_site(LampSite(enabled=False, rule="random"))
    assert not supports_site(LampSite(enabled=True, rule="random"))
    for name in SITES:
        assert supports_site(SITES[name])
    # kernel="pallas" fails on a site it does not implement; it never runs
    # the gather path in its place
    with pytest.raises(ValueError, match="random"):
        require_kernel_site(LampSite(enabled=True, rule="random"))


def test_decode_kv_bytes_model():
    """The traffic model the benchmarks report: fused < gather whenever any
    row is shorter than the full span, and never more than gather + the
    look-ahead K re-read."""
    g, f = decode_kv_bytes([5, 16, 27], n_max=4, block_size=8,
                           bytes_per_token=64, lamp=True)
    assert f < g
    g2, f2 = decode_kv_bytes([32, 32], n_max=4, block_size=8,
                             bytes_per_token=64, lamp=False)
    assert f2 == g2          # full spans, no look-ahead pass: traffic parity
    _, f3 = decode_kv_bytes([32, 32], n_max=4, block_size=8,
                            bytes_per_token=64, lamp=True)
    assert f3 == g2 * 3 // 2  # + one K stream for the smax pass


def _grid_brute(starts, qlens, rows, Bb, n_max, bs, block_q, window, width):
    """Walk one head of one pass of the prefill grid step by step. A step
    is live when kv_map's clamp leaves its block index where it is, for a
    real row and a tile that holds live queries; the kernel's own `live`
    predicate (_pre_mask) must say the same."""
    wq = width if block_q is None else min(block_q, width)
    n_q = width // wq
    live = walked = 0
    for b in range(Bb):
        for t in range(n_q):
            for j in range(n_max):
                walked += 1
                if b >= rows:
                    continue
                q0 = starts[b] + t * wq
                qe = min(max(qlens[b] - t * wq, 0), wq)
                qm = min(max(qlens[b] - t * wq, 1), wq)      # as in kv_map
                hi = min((q0 + qm - 1) // bs, n_max - 1)
                lo = 0 if window is None else min(
                    max(q0 - window + 1, 0) // bs, hi)
                clamped = min(max(j, lo), hi)
                on_live = qe > 0 and clamped == j
                pre_live = qe > 0 and j * bs <= q0 + qe - 1 and (
                    window is None or (j + 1) * bs - 1 > q0 - window)
                assert on_live == pre_live, (b, t, j)
                live += on_live
    return live, walked


GRID_CASES = {
    # (starts, qlens, rows, Bb, n_max, bs, block_q, window, width)
    "decode": ([5, 16, 27, 40], [1, 1, 1, 1], 4, 4, 8, 8, None, None, 1),
    "mixed": ([0, 30, 7], [16, 1, 9], 3, 4, 8, 8, None, None, 16),
    "pad-rows": ([3], [1], 1, 8, 8, 8, None, None, 1),
    "last-block": ([63, 56], [1, 8], 2, 2, 8, 8, None, None, 8),
    "window": ([20, 50, 3], [1, 8, 4], 3, 4, 8, 8, None, 12, 8),
    "block-q": ([0, 10, 33], [16, 3, 9], 3, 4, 8, 8, 8, None, 16),
    "block-q-window": ([0, 10, 33], [16, 3, 9], 3, 4, 8, 8, 4, 10, 16),
}


@pytest.mark.parametrize("case", sorted(GRID_CASES))
def test_grid_blocks_brute_force(case):
    starts, qlens, rows, Bb, n_max, bs, block_q, window, width = \
        GRID_CASES[case]
    # the engine's arrays are Bb long; pad entries must not count
    pad = Bb - rows
    got = grid_blocks(starts + [0] * pad, qlens + [1] * pad, rows, Bb, n_max,
                      bs, block_q=block_q, window=window,
                      width=width if block_q else None)
    want = _grid_brute(starts, qlens, rows, Bb, n_max, bs, block_q, window,
                       width)
    assert got == want
    assert 0 < got[0] <= got[1]


def test_grid_blocks_decode_matches_kv_traffic():
    """A decode launch's live grid steps are the live blocks the traffic
    model counts (fused bytes = live blocks x (2K + V))."""
    lengths, n_max, bs = [5, 16, 27, 64], 8, 8
    live, walked = grid_blocks([n - 1 for n in lengths], [1] * 4, 4, 4,
                               n_max, bs)
    _, fused = decode_kv_bytes(lengths, n_max=n_max, block_size=bs,
                               bytes_per_token=1, lamp=True)
    assert live * bs * 3 == fused
    assert walked == 4 * n_max
    with pytest.raises(ValueError, match="width"):
        grid_blocks([0], [4], 1, 1, n_max, bs, block_q=4)


# ------------------------------------------------------------ fuzz harness

def _fuzz_decode_case(seed, lengths):
    check_decode(seed, list(lengths), 4, 4, SITES["relaxed-g0"])


def _fuzz_prefill_case(seed, starts):
    check_prefill(seed, list(starts), 4, 4, SITES["relaxed-g0"], W=4)


def test_decode_seeded_fuzz_walk():
    """Non-hypothesis fallback: a seeded walk over random block tables,
    ragged lengths, and window offsets (same ops as the hypothesis case)."""
    rng = np.random.default_rng(42)
    for _ in range(12):
        _fuzz_decode_case(int(rng.integers(1 << 16)),
                          rng.integers(1, 17, size=3))
        _fuzz_prefill_case(int(rng.integers(1 << 16)),
                           rng.integers(0, 13, size=3))


try:
    import hypothesis
    from hypothesis import given, strategies as st

    @given(seed=st.integers(0, 2 ** 16 - 1),
           lengths=st.lists(st.integers(1, 16), min_size=3, max_size=3))
    def test_decode_hypothesis_fuzz(seed, lengths):
        _fuzz_decode_case(seed, lengths)

    @given(seed=st.integers(0, 2 ** 16 - 1),
           starts=st.lists(st.integers(0, 12), min_size=3, max_size=3))
    def test_prefill_hypothesis_fuzz(seed, starts):
        _fuzz_prefill_case(seed, starts)

    @pytest.mark.slow
    @hypothesis.settings(max_examples=200, deadline=None, derandomize=False,
                         print_blob=True)
    @given(seed=st.integers(0, 2 ** 20 - 1),
           lengths=st.lists(st.integers(1, 32), min_size=2, max_size=4),
           site_name=st.sampled_from(sorted(SITES)),
           window=st.sampled_from([None, 8, 20]))
    def test_decode_deep_fuzz(seed, lengths, site_name, window):
        """Opt-in random deep fuzz (-m slow): bigger spans, every rule,
        sliding windows."""
        # pad the batch so the jit cache stays bounded across examples
        lengths = (lengths + [1, 1, 1, 1])[:4]
        check_decode(seed, lengths, 8, 4, SITES[site_name], window=window)
except ImportError:  # pragma: no cover - hypothesis is optional
    pass
