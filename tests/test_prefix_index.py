"""The radix prefix index under eviction (host bookkeeping, no device).

An eviction unlinks the victim's path and builds nothing.  What that
must never change is pinned here against the index as it was before:
an oracle whose every eviction throws the trie away and builds it anew
from the surviving entries (kept ONLY in this file).  Same victims,
same donors, same trie node for node; and, independent of any oracle,
the invariant that makes unlinking safe — every ``node.entry`` is the
most recently inserted live entry through that node, and no node
outlives the entries that passed through it.
"""

import random

import pytest

from skycomputing_tpu.serving import paging
from skycomputing_tpu.serving.paging import (
    PagedKVCachePool,
    RadixPrefixIndex,
)

pytestmark = pytest.mark.serving


class RebuiltIndex(RadixPrefixIndex):
    """The index with the eviction it had before: delete the LRU entry,
    then rebuild the whole trie from the survivors in insertion order."""

    def evict_lru(self, protect=()):
        victims = [
            e for k, e in self._entries.items() if k != tuple(protect)
        ]
        if not victims:
            return None
        victim = min(victims, key=lambda e: e.stamp)
        del self._entries[victim.tokens]
        self._root = paging._TrieNode()
        for entry in self._entries.values():
            node = self._root
            node.entry = entry
            for t in entry.tokens:
                node = node.children.setdefault(t, paging._TrieNode())
                node.entry = entry
        return victim


def trie_nodes(index):
    """``{root path: node}`` for every node of the index's trie."""
    out = {}
    stack = [((), index._root)]
    while stack:
        prefix, node = stack.pop()
        out[prefix] = node
        for t, child in node.children.items():
            stack.append((prefix + (t,), child))
    return out


def trie_shape(index):
    """The trie as plain data: each node's child keys and whose entry
    it names."""
    return {
        prefix: (
            sorted(node.children),
            None if node.entry is None else node.entry.tokens,
        )
        for prefix, node in trie_nodes(index).items()
    }


def assert_invariant(index):
    """A node exists iff a live entry passes through it; ``entry`` is
    the most recently inserted of those and ``ends`` the one that stops
    there — live members of ``_entries`` themselves, not equal copies."""
    entries = list(index._entries.values())
    nodes = trie_nodes(index)
    want = {()}
    for e in entries:
        assert index._entries[e.tokens] is e
        want.update(e.tokens[:d] for d in range(1, len(e.tokens) + 1))
    assert set(nodes) == want
    for prefix, node in nodes.items():
        through = [e for e in entries if e.tokens[:len(prefix)] == prefix]
        newest = max(through, key=lambda e: e.born, default=None)
        assert node.entry is newest, prefix
        assert node.ends is index._entries.get(prefix), prefix


def random_tokens(rng, vocab, max_len):
    return tuple(
        rng.randrange(vocab) for _ in range(rng.randint(1, max_len))
    )


def random_ops(rng, vocab, n_ops, max_len=9):
    """``n_ops`` seeded operations over a vocabulary small enough that
    prompts nest and collide: inserts (some of a key already there),
    lookups, evictions (some protecting a live key, some a key that is
    not there)."""
    live = []
    for _ in range(n_ops):
        roll = rng.random()
        if roll < 0.45 or not live:
            tokens = (rng.choice(live) if live and rng.random() < 0.1
                      else random_tokens(rng, vocab, max_len))
            live.append(tokens)
            yield "insert", tokens
        elif roll < 0.75:
            yield "lookup", random_tokens(rng, vocab, max_len + 2)
        else:
            protect = rng.choice(
                [(), rng.choice(live), random_tokens(rng, vocab, max_len)]
            )
            yield "evict", protect


@pytest.mark.parametrize("seed", range(4))
@pytest.mark.parametrize("vocab", [2, 3, 50])
def test_unlink_matches_rebuild_from_survivors(vocab, seed):
    """Differential: the same operations on the index and on the
    rebuilding oracle give the same victim, the same ``(depth, donor)``
    for every lookup, and the same trie node for node, at every step."""
    rng = random.Random(1000 * vocab + seed)
    index, oracle = RadixPrefixIndex(), RebuiltIndex()
    evictions = 0
    for step, (op, tokens) in enumerate(random_ops(rng, vocab, 400)):
        if op == "insert":
            pages = (step,)
            assert index.insert(tokens, pages) == oracle.insert(
                tokens, pages)
        elif op == "lookup":
            depth, donor = index.lookup_entry(tokens)
            want_depth, want = oracle.lookup_entry(tokens)
            assert depth == want_depth
            assert (donor and donor.tokens) == (want and want.tokens)
            assert index.lookup(tokens) == oracle.lookup(tokens)
        else:
            victim = index.evict_lru(tokens)
            want = oracle.evict_lru(tokens)
            assert (victim and victim.tokens) == (want and want.tokens)
            assert victim is None or victim.tokens != tokens
            evictions += victim is not None
        assert len(index) == len(oracle)
        assert trie_shape(index) == trie_shape(oracle), (step, op, tokens)
    assert evictions > 50


@pytest.mark.parametrize("seed", range(3))
@pytest.mark.parametrize("vocab", [2, 50])
def test_no_stale_entry_and_no_orphan_node(vocab, seed):
    """The invariant itself, after every operation of a random run
    that also empties the index (``clear``) and evicts it dry."""
    rng = random.Random(77 * vocab + seed)
    index = RadixPrefixIndex()
    for step, (op, tokens) in enumerate(random_ops(rng, vocab, 250)):
        if op == "insert":
            index.insert(tokens, (step,))
        elif op == "lookup":
            index.lookup_entry(tokens)
        else:
            index.evict_lru(tokens)
        if step == 120:
            assert len(index.clear()) > 0
        assert_invariant(index)
    while index.evict_lru() is not None:
        assert_invariant(index)
    assert len(index) == 0
    assert index._root.children == {} and index._root.entry is None


def test_eviction_constructs_no_node(monkeypatch):
    """The cost, as counts: evicting from 100 entries of 200 tokens
    constructs no trie node (the rebuild constructed ~20,000), and the
    trie then holds exactly the survivors' nodes."""
    rng = random.Random(5)
    index = RadixPrefixIndex()
    # groups of ten share a 40-token opening, so victims' paths fork
    # off kept ones as well as standing alone
    openings = [tuple(rng.randrange(50257) for _ in range(40))
                for _ in range(10)]
    for i in range(100):
        tail = tuple(rng.randrange(50257) for _ in range(160))
        index.insert(openings[i % 10] + tail, (i,))
    assert len(index) == 100

    built = []
    init = paging._TrieNode.__init__

    def counting_init(self):
        built.append(1)
        init(self)

    monkeypatch.setattr(paging._TrieNode, "__init__", counting_init)
    for _ in range(15):
        assert index.evict_lru() is not None
    assert built == []
    monkeypatch.undo()

    survivors = list(index._entries.values())
    assert len(survivors) == 85
    paths = {()}
    for e in survivors:
        paths.update(e.tokens[:d] for d in range(1, 201))
    assert set(trie_nodes(index)) == paths
    assert_invariant(index)


@pytest.mark.parametrize("vocab", [50257, 3])
def test_pool_under_eviction_pressure_matches_oracle(vocab):
    """Pool level, the saturated cell's shape at small scale: four
    rows over 48 pages, every admitted prompt indexed, the index
    holding finished prompts' pages until an admission evicts them.
    Unshared prompts (vocabulary 50257) and, as the control that does
    share, a vocabulary of 3 (hits, copy-on-write grants, a protected
    donor).  Same grants and the same ``prefix_evictions`` as a pool on
    the rebuilding oracle; both consistent at every step."""
    def make(index_cls):
        pool = PagedKVCachePool(num_pages=48, page_size=4,
                                max_pages_per_request=12)
        pool.index = index_cls(pool.index.max_entries)
        return pool

    pool, oracle = make(RadixPrefixIndex), make(RebuiltIndex)
    rng = random.Random(vocab)
    running = []
    request_id = 0
    while pool.prefix_evictions < 300:
        if len(running) == 4:
            done = running.pop(rng.randrange(4))
            assert pool.release(done) == oracle.release(done)
        prompt = random_tokens(rng, vocab, 24)
        total = len(prompt) + rng.randint(2, 12)
        request_id += 1
        grant = pool.acquire(request_id, prompt, total)
        want = oracle.acquire(request_id, prompt, total)
        assert grant is not None and want is not None
        assert grant == want
        assert (pool.register_prefix(request_id, prompt)
                == oracle.register_prefix(request_id, prompt))
        running.append(request_id)
        pool.check_consistency()
        oracle.check_consistency()
        assert pool.prefix_evictions == oracle.prefix_evictions
        assert pool.free_pages == oracle.free_pages
    assert_invariant(pool.index)
    assert trie_shape(pool.index) == trie_shape(oracle.index)
    assert (pool.prefix_hits > 0) == (vocab == 3)
    for done in running:
        pool.release(done)
    pool.drop_prefix_cache()
    pool.check_consistency()
    assert pool.free_pages == 48
