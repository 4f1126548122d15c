"""Property / differential tests for prefix-cached COW paged KV blocks and
chunked prefill.

Three layers:

  * Pool state-machine properties: random interleavings of admit (alloc +
    prefix-fork + COW), free, and defrag must preserve block conservation
    (free + unique owned == total), never double-free, keep refcounts equal
    to the number of owning sequences, and keep every block table pointing
    at live arena rows. Driven twice: a hypothesis stateful machine (the
    deep harness; skipped when hypothesis is not installed) and a seeded
    numpy random walk over the same shared ops (always runs).
  * Differential: a randomized request stream (shared-prefix groups +
    disjoint prompts, mixed temperatures) through the engine with prefix
    caching + chunked prefill ON must be token-identical to the PR-1
    configuration with both OFF, while allocating strictly fewer blocks
    whenever prefixes overlap by at least one block.
  * Chunked-prefill edge cases: chunk/block-boundary prompt lengths,
    prompts shorter than one chunk, preemption between chunks (resume
    re-prefills only the un-cached suffix), and decode steps interleaving
    mid-prefill.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.configs import get_config, reduced as reduce_cfg
from repro.models import api, transformer
from repro.serving import (EngineConfig, LampEngine, PagedKVPool,
                           SamplingParams, Sequence)
from repro.serving.kv_pool import chain_hashes


@pytest.fixture(scope="module")
def model():
    cfg = reduce_cfg(get_config("gpt2")).replace(vocab=128)
    params = api.init_params(cfg, jax.random.PRNGKey(0))
    return cfg, params


@pytest.fixture(scope="module")
def tiny_cfg():
    return reduce_cfg(get_config("gpt2")).replace(vocab=8)


def _prompt(rng, cfg, n):
    return rng.integers(0, cfg.vocab, size=n).tolist()


# ===================================================================== pool
# Shared op driver: emulates the scheduler's admission (match / cap / share /
# COW / alloc) and release paths against a real pool, then checks the full
# invariant set. Used by both the seeded fuzz walk and the hypothesis
# stateful machine.

class PoolHarness:
    def __init__(self, cfg, n_blocks=8, block_size=2, vocab=3):
        self.pool = PagedKVPool(cfg, n_blocks=n_blocks,
                                block_size=block_size,
                                enable_prefix_cache=True)
        self.vocab = vocab
        self.seqs = {}                  # seq id -> Sequence
        self.next_id = 0

    # -- ops ---------------------------------------------------------------

    def admit(self, tokens):
        """Scheduler-shaped admission; returns the seq id or None when the
        block budget cannot cover it."""
        pool, bs = self.pool, self.pool.block_size
        target = len(tokens)
        matched = pool.match_prefix(tokens)
        cached = min(len(matched) * bs, target - 1)
        kept = -(-cached // bs)
        matched = matched[:kept]
        need_new = pool.blocks_for(target) - kept
        need_cow = 1 if cached % bs else 0
        revive = sum(1 for b in matched if pool.is_cached_free(b))
        if need_new + need_cow + revive > pool.num_free:
            return None
        pool.share(matched)
        blocks = list(matched)
        if need_cow:
            blocks[-1] = pool.copy_on_write(blocks[-1])
        if need_new > 0:
            blocks.extend(pool.alloc(need_new))
        seq = Sequence(self.next_id, list(tokens), SamplingParams(),
                       float(self.next_id))
        self.next_id += 1
        seq.block_ids = blocks
        seq.cache_len = seq.prefill_cursor = target
        # "prefill done": full blocks become matchable
        pool.register_prefix(tokens, blocks, target)
        self.seqs[seq.req_id] = seq
        return seq.req_id

    def free(self, sid):
        seq = self.seqs.pop(sid)
        self.pool.free_blocks(seq.block_ids)
        seq.block_ids = []

    def rollback(self, sid, n_tokens):
        """Speculative rollback: truncate a sequence's cache to n_tokens
        (<= its current cache_len), freeing the surplus blocks and COWing a
        shared/registered partial tail."""
        seq = self.seqs[sid]
        n_tokens = min(n_tokens, seq.cache_len)
        # like admission, the caller guards the budget: copying a shared
        # partial tail needs one allocatable block once the surplus is
        # freed (the engine only rolls back into blocks the sequence wrote
        # itself, which are private)
        pool, keep = self.pool, self.pool.blocks_for(n_tokens)
        cow = (n_tokens % pool.block_size and keep
               and pool.needs_cow(seq.block_ids[keep - 1]))
        freed = sum(1 for b in seq.block_ids[keep:]
                    if pool.refcount.get(b, 0) == 1)
        if cow and pool.num_free + freed < 1:
            return
        seq.block_ids = self.pool.rollback(seq.block_ids, n_tokens)
        seq.cache_len = seq.prefill_cursor = n_tokens

    def defrag(self):
        live = sorted(self.seqs.values(), key=lambda s: s.arrival_time)
        self.pool.defrag(live)

    # -- invariants ---------------------------------------------------------

    def check(self):
        # the full oracle now lives on the pool itself (production recovery
        # paths run it too); the harness just feeds it every live owner
        self.pool.check_invariants(self.seqs.values())


def _random_tokens(rng, vocab, block_size):
    # tiny vocab + short lengths -> frequent shared prefixes and reuse
    n = int(rng.integers(1, 4 * block_size + 2))
    return rng.integers(0, vocab, size=n).tolist()


def _fuzz_step(h, rng):
    ops = ["admit", "admit", "free", "double_free", "rollback", "defrag"]
    op = ops[int(rng.integers(len(ops)))]
    if op == "admit":
        h.admit(_random_tokens(rng, h.vocab, h.pool.block_size))
    elif op == "free" and h.seqs:
        sid = list(h.seqs)[int(rng.integers(len(h.seqs)))]
        h.free(sid)
    elif op == "rollback" and h.seqs:
        sid = list(h.seqs)[int(rng.integers(len(h.seqs)))]
        h.rollback(sid, int(rng.integers(0, h.seqs[sid].cache_len + 1)))
    elif op == "double_free" and h.seqs:
        # freeing a sequence's blocks twice must raise, never corrupt
        sid = list(h.seqs)[int(rng.integers(len(h.seqs)))]
        blocks = list(h.seqs[sid].block_ids)
        h.free(sid)
        gone = [b for b in blocks if h.pool.refcount.get(b, 0) == 0]
        if gone:
            with pytest.raises(ValueError):
                h.pool.free_blocks(gone)
    elif op == "defrag":
        h.defrag()
    h.check()


def test_pool_invariants_seeded_walk(tiny_cfg):
    """Non-hypothesis fallback: 200-step random walk over the same ops."""
    for seed in range(3):
        rng = np.random.default_rng(seed)
        h = PoolHarness(tiny_cfg)
        for _ in range(200):
            _fuzz_step(h, rng)
        # drain: every request finishes -> all blocks reclaimable
        for sid in list(h.seqs):
            h.free(sid)
        h.check()
        assert h.pool.num_free == h.pool.num_total


def test_pool_double_free_raises(tiny_cfg):
    pool = PagedKVPool(tiny_cfg, n_blocks=6, block_size=2)
    a = pool.alloc(2)
    pool.free_blocks(a)
    with pytest.raises(ValueError, match="double free"):
        pool.free_blocks([a[0]])
    with pytest.raises(ValueError, match="double free"):
        pool.free_blocks([5])       # never allocated == still on free list
    with pytest.raises(ValueError, match="null block"):
        pool.free_blocks([0])
    # shared blocks need one free per owner -- premature re-free must raise
    b = pool.alloc(1)
    pool.share(b)
    pool.free_blocks(b)
    pool.free_blocks(b)           # second owner: fine
    with pytest.raises(ValueError):
        pool.free_blocks(b)       # third: double free


def test_pool_cow_and_sharing_semantics(tiny_cfg):
    pool = PagedKVPool(tiny_cfg, n_blocks=8, block_size=2,
                       enable_prefix_cache=True)
    tokens = [1, 0, 1, 1]                      # two full blocks
    blocks = pool.alloc(2)
    pool.register_prefix(tokens, blocks, 4)
    assert pool.match_prefix(tokens) == blocks
    assert pool.match_prefix([1, 0, 7, 7]) == blocks[:1]
    assert pool.match_prefix([0, 0, 1, 1]) == []
    # a second owner forks the full prefix
    pool.share(blocks)
    assert pool.refcount[blocks[0]] == 2
    # shared + registered blocks must be COW'd before writing
    assert pool.needs_cow(blocks[1])
    new = pool.copy_on_write(blocks[1])
    assert new != blocks[1]
    assert pool.refcount[blocks[1]] == 1 and pool.refcount[new] == 1
    assert not pool.needs_cow(new)
    # the forker releases its share; the original owner still holds block 0
    pool.free_blocks(blocks)
    assert pool.match_prefix(tokens) == blocks
    assert pool.is_cached_free(blocks[1])
    assert pool.refcount[blocks[0]] == 1
    # the original owner and the COW copy go too: registered blocks stay
    # matchable (cached-free) ...
    pool.free_blocks([blocks[0], new])
    assert pool.is_cached_free(blocks[0])
    assert pool.match_prefix(tokens) == blocks
    # ... until eviction reclaims them under pressure
    got = pool.alloc(pool.num_free)
    assert set(blocks) <= set(got), "cached-free blocks must be reclaimable"
    assert pool.match_prefix(tokens) == []


def test_pool_rollback_frees_surplus_and_conserves(tiny_cfg):
    pool = PagedKVPool(tiny_cfg, n_blocks=10, block_size=2)
    blocks = pool.alloc(4)                      # covers 8 tokens
    kept = pool.rollback(blocks, 3)             # 3 tokens -> 2 blocks
    assert kept == blocks[:2]
    assert pool.num_free == pool.num_total - 2
    assert pool.refcount == {blocks[0]: 1, blocks[1]: 1}
    # surplus is really free: re-freeing it raises (double free)
    with pytest.raises(ValueError, match="double free"):
        pool.free_blocks([blocks[2]])
    # rollback to zero releases everything
    assert pool.rollback(kept, 0) == []
    assert pool.num_free == pool.num_total
    # rollback cannot keep more blocks than the sequence owns
    with pytest.raises(ValueError, match="rollback"):
        pool.rollback([], 1)


def test_pool_rollback_cow_never_mutates_shared_block(tiny_cfg):
    """Rolling back into a COW-shared tail must copy, not mutate: the other
    owner's arena row is untouched and its table still points at it."""
    pool = PagedKVPool(tiny_cfg, n_blocks=8, block_size=2,
                       enable_prefix_cache=True)
    blocks = pool.alloc(3)                      # seq A: 6 tokens
    pool.share(blocks)                          # seq B shares all three
    ids = jnp.arange(pool.n_blocks, dtype=jnp.float32)
    pool.k = jnp.ones_like(pool.k) * ids[None, :, None, None, None]
    # A rolls back to 3 tokens: block 2 freed (B still owns it), block 1
    # becomes A's partial tail -> must be COW'd off the shared copy
    kept = pool.rollback(list(blocks), 3)
    assert kept[0] == blocks[0]
    assert kept[1] != blocks[1], "shared partial tail must be copied"
    assert pool.refcount[blocks[0]] == 2        # still shared
    assert pool.refcount[blocks[1]] == 1        # B's copy survives
    assert pool.refcount[blocks[2]] == 1
    assert pool.refcount[kept[1]] == 1
    # the shared row's contents were copied, not moved or zeroed
    assert float(pool.k[0, blocks[1], 0, 0, 0]) == blocks[1]
    assert float(pool.k[0, kept[1], 0, 0, 0]) == blocks[1]
    assert not pool.needs_cow(kept[1])


def test_pool_rollback_registered_tail_cow_and_index_survival(tiny_cfg):
    """Rollback into a registered (prefix-indexed) block COWs the partial
    tail; the index keeps mapping the original block with its contents."""
    pool = PagedKVPool(tiny_cfg, n_blocks=8, block_size=2,
                       enable_prefix_cache=True)
    tokens = [1, 0, 1, 1, 0, 0]
    blocks = pool.alloc(3)
    pool.register_prefix(tokens, blocks, 6)     # all three blocks indexed
    kept = pool.rollback(list(blocks), 3)       # mid-block cap in block 1
    assert kept[0] == blocks[0]
    assert kept[1] != blocks[1], "registered partial tail must be copied"
    # the index still maps the original chain (contents never mutated);
    # freed/copied-off blocks sit on the cached-free LRU, still matchable
    assert pool.match_prefix(tokens) == blocks
    assert pool.is_cached_free(blocks[1]) and pool.is_cached_free(blocks[2])
    # block-aligned rollback keeps the (full, registered) tail without COW
    kept2 = pool.rollback(kept, 2)
    assert kept2 == kept[:1]


def test_engine_rejects_zero_prefill_budget(model):
    cfg, params = model
    with pytest.raises(ValueError, match="max_prefill_tokens"):
        LampEngine(cfg, params, EngineConfig(block_size=4, max_model_len=64,
                                             max_prefill_tokens=0))


def test_match_verifies_content_not_just_hash(tiny_cfg):
    """A chain-hash collision (same hash, different tokens) must degrade to
    a cache miss, never map a request onto foreign KV blocks."""
    pool = PagedKVPool(tiny_cfg, n_blocks=8, block_size=2,
                       enable_prefix_cache=True)
    a = [1, 0, 1, 1]
    blocks = pool.alloc(2)
    pool.register_prefix(a, blocks, 4)
    forged = chain_hashes(a, 2)   # "colliding" hashes for different tokens
    assert pool.match_prefix([2, 2, 2, 2], hashes=forged) == []
    assert pool.match_prefix([1, 0, 2, 2], hashes=forged) == blocks[:1]
    assert pool.match_prefix(a, hashes=forged) == blocks


def test_chain_hashes_prefix_property():
    a = [1, 2, 3, 4, 5, 6]
    b = [1, 2, 3, 4, 9, 9]
    ha, hb = chain_hashes(a, 2), chain_hashes(b, 2)
    assert ha[:2] == hb[:2] and ha[2] != hb[2]
    assert chain_hashes(a, 2, 5) == ha[:2]     # partial coverage: full blocks
    # equal block content at different depth must not collide
    assert chain_hashes([7, 7, 7, 7], 2)[0] != chain_hashes([7, 7, 7, 7], 2)[1]


# The hypothesis stateful machine: the deep property harness. Import-guarded
# (not importorskip) so the seeded fallback tests above still run without
# hypothesis installed; CI pins the "ci" profile (derandomized, 500
# examples) via HYPOTHESIS_PROFILE -- see tests/conftest.py.
try:
    import hypothesis
    from hypothesis import strategies as st
    from hypothesis.stateful import (RuleBasedStateMachine, initialize,
                                     invariant, rule)
    HAVE_HYPOTHESIS = True
except ImportError:
    HAVE_HYPOTHESIS = False

if HAVE_HYPOTHESIS:
    TOKENS = st.lists(st.integers(0, 2), min_size=1, max_size=10)

    class PoolStateMachine(RuleBasedStateMachine):
        cfg = None  # injected by the test

        @initialize()
        def setup(self):
            self.h = PoolHarness(type(self).cfg)

        @rule(tokens=TOKENS)
        def admit(self, tokens):
            self.h.admit(tokens)

        @rule(idx=st.integers(0, 1 << 30))
        def free(self, idx):
            if self.h.seqs:
                self.h.free(list(self.h.seqs)[idx % len(self.h.seqs)])

        @rule(idx=st.integers(0, 1 << 30))
        def double_free_rejected(self, idx):
            """Freeing any sequence's blocks twice must raise, not corrupt."""
            if not self.h.seqs:
                return
            sid = list(self.h.seqs)[idx % len(self.h.seqs)]
            blocks = list(self.h.seqs[sid].block_ids)
            self.h.free(sid)
            gone = [b for b in blocks if self.h.pool.refcount.get(b, 0) == 0]
            if gone:
                with pytest.raises(ValueError):
                    # blocks that actually went free: re-freeing must fault
                    # (still-shared ones would just drop another owner)
                    self.h.pool.free_blocks(gone)

        @rule(idx=st.integers(0, 1 << 30), frac=st.floats(0.0, 1.0))
        def rollback(self, idx, frac):
            """Speculative rollback to any point in a sequence's cache must
            conserve blocks, never corrupt shared/registered state, and
            leave a writable (private) partial tail."""
            if not self.h.seqs:
                return
            sid = list(self.h.seqs)[idx % len(self.h.seqs)]
            n = int(frac * self.h.seqs[sid].cache_len)
            self.h.rollback(sid, n)

        @rule()
        def defrag(self):
            self.h.defrag()

        @invariant()
        def pool_invariants(self):
            if hasattr(self, "h"):
                self.h.check()


@pytest.mark.skipif(not HAVE_HYPOTHESIS, reason="hypothesis not installed")
def test_pool_state_machine(tiny_cfg):
    PoolStateMachine.cfg = tiny_cfg
    hypothesis.stateful.run_state_machine_as_test(PoolStateMachine)


@pytest.mark.slow
@pytest.mark.skipif(not HAVE_HYPOTHESIS, reason="hypothesis not installed")
def test_pool_state_machine_deep(tiny_cfg):
    """Opt-in deep fuzz (pytest -m slow): many more examples per run."""
    PoolStateMachine.cfg = tiny_cfg
    hypothesis.stateful.run_state_machine_as_test(
        PoolStateMachine,
        settings=hypothesis.settings(max_examples=300, deadline=None,
                                     stateful_step_count=80))


# ============================================================== differential

def _staggered_run(cfg, params, reqs, *, prefix_cache, chunked_prefill,
                   n_blocks=0, max_prefill_tokens=8):
    """One engine pass, arrivals staggered one step apart so later requests
    can hit earlier requests' registered prefixes."""
    engine = LampEngine(cfg, params, EngineConfig(
        block_size=4, max_model_len=64, max_prefill_batch=4,
        max_decode_batch=8, n_blocks=n_blocks,
        max_prefill_tokens=max_prefill_tokens,
        prefix_cache=prefix_cache, chunked_prefill=chunked_prefill))
    outs = []
    for prompt, sampling in reqs:
        engine.add_request(prompt, sampling)
        outs.extend(engine.step())
    outs.extend(engine.run_to_completion())
    return engine, {o.req_id: o for o in outs}


def test_differential_vs_pr1_baseline(model):
    """Prefix caching + chunked prefill ON == both OFF, token for token,
    with strictly fewer blocks allocated (prefixes overlap >= one block)."""
    cfg, params = model
    rng = np.random.default_rng(11)
    shared_a = _prompt(rng, cfg, 12)           # 3 full blocks at bs=4
    shared_b = _prompt(rng, cfg, 8)
    reqs = []
    for i in range(9):
        if i % 3 == 0:
            prompt = shared_a + _prompt(rng, cfg, int(rng.integers(1, 8)))
        elif i % 3 == 1:
            prompt = shared_b + _prompt(rng, cfg, int(rng.integers(1, 8)))
        else:
            prompt = _prompt(rng, cfg, int(rng.integers(3, 20)))
        temp = 0.0 if i % 2 else 0.7
        reqs.append((prompt, SamplingParams(
            max_new_tokens=int(rng.integers(2, 8)), seed=i,
            temperature=temp)))

    on, on_outs = _staggered_run(cfg, params, reqs,
                                 prefix_cache=True, chunked_prefill=True)
    off, off_outs = _staggered_run(cfg, params, reqs,
                                   prefix_cache=False, chunked_prefill=False)
    assert len(on_outs) == len(off_outs) == len(reqs)
    for i in range(len(reqs)):
        assert on_outs[i].tokens == off_outs[i].tokens, f"req {i}"
    s_on, s_off = on.stats(), off.stats()
    assert s_on["blocks_allocated"] < s_off["blocks_allocated"]
    assert s_on["blocks_saved"] > 0
    assert s_on["cached_tokens"] > 0
    assert s_off["blocks_saved"] == 0 and s_off["cached_tokens"] == 0
    # all blocks returned in both configurations
    assert on.pool.num_used == 0 and off.pool.num_used == 0


def test_paged_prefill_window_matches_full(model):
    """Splitting a prompt into windows must reproduce the full prefill's
    last-position logits exactly (same gathered width, row-wise compute)."""
    cfg, params = model
    rng = np.random.default_rng(12)
    prompt = _prompt(rng, cfg, 10)
    bs = 4
    for use_lamp in (False, True):
        arenas = [transformer.init_paged_cache(cfg, 16, bs, jnp.float32)
                  for _ in range(2)]
        bt = jnp.asarray(np.array([[1, 2, 3, 0, 0, 0, 0, 0]], np.int32))
        tokens = np.zeros((1, 16), np.int32)
        tokens[0, :10] = prompt
        full, _, _ = transformer.paged_prefill(
            cfg, params, jnp.asarray(tokens), arenas[0], bt,
            jnp.asarray([10], jnp.int32), use_lamp=use_lamp)
        # two windows: 6 tokens then 4 tokens
        w1 = np.zeros((1, 8), np.int32)
        w1[0, :6] = prompt[:6]
        _, arena, _ = transformer.paged_prefill_window(
            cfg, params, jnp.asarray(w1), arenas[1], bt,
            jnp.asarray([0], jnp.int32), jnp.asarray([6], jnp.int32),
            use_lamp=use_lamp)
        w2 = np.zeros((1, 4), np.int32)
        w2[0, :4] = prompt[6:]
        split, _, _ = transformer.paged_prefill_window(
            cfg, params, jnp.asarray(w2), arena, bt,
            jnp.asarray([6], jnp.int32), jnp.asarray([4], jnp.int32),
            use_lamp=use_lamp)
        np.testing.assert_array_equal(np.asarray(full), np.asarray(split))


# ========================================================== chunk edge cases

@pytest.mark.parametrize("plen", [4, 8, 16, 3, 17, 9])
def test_chunk_and_block_boundaries(model, plen):
    """Prompt lengths on / off chunk (8) and block (4) boundaries, shorter
    than one chunk, and spanning several chunks: identical to baseline."""
    cfg, params = model
    rng = np.random.default_rng(13)
    reqs = [(_prompt(rng, cfg, plen), SamplingParams(max_new_tokens=4))]
    _, on = _staggered_run(cfg, params, reqs, prefix_cache=True,
                           chunked_prefill=True, max_prefill_tokens=8)
    _, off = _staggered_run(cfg, params, reqs, prefix_cache=False,
                            chunked_prefill=False)
    assert on[0].tokens == off[0].tokens


def test_cow_on_block_aligned_duplicate(model):
    """An exact duplicate of a block-aligned prompt matches every full
    block; the prompt-1 cap lands mid-block, forcing one COW copy."""
    cfg, params = model
    rng = np.random.default_rng(14)
    prompt = _prompt(rng, cfg, 8)              # 2 full blocks at bs=4
    reqs = [(prompt, SamplingParams(max_new_tokens=4, seed=0)),
            (prompt, SamplingParams(max_new_tokens=4, seed=1))]
    engine, outs = _staggered_run(cfg, params, reqs, prefix_cache=True,
                                  chunked_prefill=True)
    assert outs[0].tokens == outs[1].tokens    # greedy + same prompt
    assert outs[1].num_cached_tokens == len(prompt) - 1
    assert engine.pool.cow_copies >= 1
    _, off = _staggered_run(cfg, params, reqs, prefix_cache=False,
                            chunked_prefill=False)
    for i in range(2):
        assert outs[i].tokens == off[i].tokens


def test_preemption_under_pressure_identical_outputs(model):
    """Heavy churn (preemptions, chunked prefill, prefix cache all active)
    must not change any request's output vs an unconstrained pool."""
    cfg, params = model
    rng = np.random.default_rng(15)
    reqs = [(_prompt(rng, cfg, int(rng.integers(16, 40))),
             SamplingParams(max_new_tokens=8, seed=i,
                            temperature=0.6 if i % 2 else 0.0))
            for i in range(6)]
    big, big_outs = _staggered_run(cfg, params, reqs, prefix_cache=True,
                                   chunked_prefill=True, n_blocks=200)
    small, small_outs = _staggered_run(cfg, params, reqs, prefix_cache=True,
                                       chunked_prefill=True, n_blocks=20)
    assert big.num_preemptions == 0
    assert small.num_preemptions > 0
    for i in range(len(reqs)):
        assert big_outs[i].tokens == small_outs[i].tokens, f"req {i}"
    assert small.pool.num_used == 0


def test_preempt_between_chunks_resume_suffix_only(model):
    """A long prompt preempted mid-(chunked-)prefill re-admits against its
    own registered blocks: the resume prefills only the un-cached suffix."""
    cfg, params = model
    rng = np.random.default_rng(18)
    short = _prompt(rng, cfg, 4)
    long = _prompt(rng, cfg, 32)

    def run(prefix_cache):
        # pool sized so A's decode growth collides with B's chunked prefill:
        # B (youngest) is preempted mid-prefill and later resumed
        engine = LampEngine(cfg, params, EngineConfig(
            block_size=4, max_model_len=40, n_blocks=12,
            max_prefill_tokens=8, prefix_cache=prefix_cache,
            chunked_prefill=True))
        a = engine.add_request(short, SamplingParams(max_new_tokens=16,
                                                     seed=0))
        engine.step()                      # A prefills, starts decoding
        b = engine.add_request(long, SamplingParams(max_new_tokens=4,
                                                    seed=1))
        engine.run_to_completion()
        outs = {o.req_id: o for o in engine._finished}
        return engine, outs[a].tokens, outs[b].tokens

    on, a_on, b_on = run(True)
    off, a_off, b_off = run(False)
    assert on.num_preemptions > 0 and off.num_preemptions > 0
    # identical outputs with and without the cache ...
    assert a_on == a_off and b_on == b_off
    # ... but the resume re-used B's registered chunk blocks instead of
    # re-running the whole prompt. Own-KV resume hits are accounted as
    # resume_cached_tokens (not cached_tokens, which tracks cross-request
    # prefix sharing only -- no request here shares a prefix)
    assert on.stats()["resume_cached_tokens"] > 0
    assert on.stats()["cached_tokens"] == 0
    assert on.prefill_tokens_run < off.prefill_tokens_run
    assert on.pool.num_used == 0 and off.pool.num_used == 0


def test_decode_interleaves_mid_prefill(model):
    """While a long prompt prefills in chunks, an already-decoding request
    keeps producing tokens between the chunks."""
    cfg, params = model
    rng = np.random.default_rng(16)
    engine = LampEngine(cfg, params, EngineConfig(
        block_size=4, max_model_len=64, max_prefill_tokens=4,
        prefix_cache=True, chunked_prefill=True))
    a = engine.add_request(_prompt(rng, cfg, 4),
                           SamplingParams(max_new_tokens=12, seed=0))
    engine.step()                              # A prefills, starts decoding
    b = engine.add_request(_prompt(rng, cfg, 24),
                           SamplingParams(max_new_tokens=4, seed=1))
    kinds = []
    while engine.has_unfinished():
        pre, dec = engine.prefill_steps, engine.decode_steps
        engine.step()
        p = engine.prefill_steps > pre
        d = engine.decode_steps > dec
        kinds.append("b" if p and d else "p" if p else "d")
    trace = "".join(kinds)
    # B needs 6 chunks of 4; A must decode between (split phases) or
    # within (fused mixed step, "b") those chunks
    assert trace.count("p") + trace.count("b") >= 6
    assert "b" in trace or ("pd" in trace and "dp" in trace), trace
    assert engine.prefill_chunks >= 5
    outs = {o.req_id: o for o in engine._finished}
    assert len(outs[a].tokens) == 12 and len(outs[b].tokens) == 4


def test_defrag_with_shared_blocks(model):
    """Refcount-aware defrag: shared blocks map to one new row, every
    sharer's table is rewritten, refcounts and the prefix index survive."""
    cfg, params = model
    rng = np.random.default_rng(17)
    shared = _prompt(rng, cfg, 12)
    reqs = [(shared + _prompt(rng, cfg, 3 + i),
             SamplingParams(max_new_tokens=6, seed=i)) for i in range(3)]

    def run(defrag_every):
        engine = LampEngine(cfg, params, EngineConfig(
            block_size=4, max_model_len=64, n_blocks=40,
            max_prefill_tokens=8, prefix_cache=True, chunked_prefill=True))
        outs = []
        for prompt, sampling in reqs:
            engine.add_request(prompt, sampling)
            outs.extend(engine.step())
        step = 0
        while engine.has_unfinished():
            outs.extend(engine.step())
            step += 1
            if defrag_every and step % defrag_every == 0:
                engine.defrag()
        assert engine.pool.num_used == 0
        return {o.req_id: o.tokens for o in outs}

    assert run(0) == run(1)
