"""Data-pipeline determinism + tier-movement semantics."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.offload import (DEVICE, PINNED_HOST, HostPool, TierExecutor,
                                backend_memory_kinds, put_tier, tier_of,
                                tree_put_tier, nbytes_of)
from repro.data.pipeline import DataConfig, SyntheticLM, make_dataset


class TestData:
    def test_deterministic_in_step(self):
        cfg = DataConfig(vocab_size=128, seq_len=32, global_batch=4, seed=3)
        ds = SyntheticLM(cfg)
        a, b = ds.batch(7), ds.batch(7)
        np.testing.assert_array_equal(a["tokens"], b["tokens"])
        c = ds.batch(8)
        assert not np.array_equal(a["tokens"], c["tokens"])

    def test_host_sharding_partitions_batch(self):
        """Two hosts' shards at the same step are disjoint deterministic
        streams, each carrying its slice of the global batch."""
        h0 = SyntheticLM(DataConfig(128, 32, 4, seed=3, shard=(0, 2)))
        h1 = SyntheticLM(DataConfig(128, 32, 4, seed=3, shard=(1, 2)))
        assert h0.batch(5)["tokens"].shape[0] == 2
        assert h1.batch(5)["tokens"].shape[0] == 2
        assert not np.array_equal(h0.batch(5)["tokens"],
                                  h1.batch(5)["tokens"])

    def test_labels_are_shifted_tokens(self):
        ds = SyntheticLM(DataConfig(128, 16, 2, seed=0))
        b = ds.batch(0)
        # learnable structure: ~90% of successors follow the chain
        succ = ds._succ
        match = (succ[b["tokens"][:, :-1]] == b["tokens"][:, 1:]).mean()
        assert match > 0.7

    def test_token_file_backend(self, tmp_path):
        path = str(tmp_path / "toks.bin")
        np.arange(10_000, dtype=np.int32).tofile(path)
        cfg = DataConfig(vocab_size=1 << 20, seq_len=64, global_batch=2)
        ds = make_dataset(cfg, path)
        b = ds.batch(0)
        assert b["tokens"].shape == (2, 64)
        np.testing.assert_array_equal(b["tokens"][:, 1:], b["labels"][:, :-1])


class TestTiers:
    def test_put_tier_roundtrip(self):
        if PINNED_HOST not in backend_memory_kinds():
            pytest.skip("no host memory kinds on this backend")
        x = jnp.arange(16.0).reshape(4, 4)
        h = put_tier(x, PINNED_HOST)
        assert tier_of(h) == PINNED_HOST
        d = put_tier(h, DEVICE)
        assert tier_of(d) == DEVICE
        np.testing.assert_array_equal(np.asarray(d), np.asarray(x))

    def test_host_slice_cleared_to_device(self):
        """Slices of host arrays must come back fully device-spaced (an
        eager slice keeps a host aval under a device sharding)."""
        if PINNED_HOST not in backend_memory_kinds():
            pytest.skip("no host memory kinds")
        pool = put_tier(jnp.zeros((4, 2, 2)), PINNED_HOST)
        y = put_tier(pool[1], DEVICE)
        # mixing into dynamic_update_slice must not raise
        out = jax.lax.dynamic_update_slice(jnp.ones((2, 2)), y, (0, 0))
        assert float(out.sum()) == 0.0

    def test_tree_put_tier_and_nbytes(self):
        tree = {"a": jnp.zeros((8,), jnp.float32),
                "b": jnp.zeros((2, 2), jnp.bfloat16)}
        assert nbytes_of(tree) == 32 + 8
        if PINNED_HOST in backend_memory_kinds():
            ht = tree_put_tier(tree, PINNED_HOST)
            assert all(tier_of(l) == PINNED_HOST
                       for l in jax.tree_util.tree_leaves(ht))

    def test_host_pool_moves_only_named_pages(self):
        """Multi-page writes then reads through a pinned_host LMB pool:
        the pool stays in host memory, the contents round-trip, and only
        the named pages move (metered bytes and untouched slots)."""
        moved = []
        ex = TierExecutor(meter=moved.append)
        pool = ex.alloc_pool(8, (4, 3), jnp.bfloat16, tier="lmb")
        assert isinstance(pool, HostPool)
        page_bytes = 4 * 3 * 2
        before = list(pool.pages)
        data = jnp.arange(3 * 12, dtype=jnp.bfloat16).reshape(3, 4, 3)
        pool = ex.write_pages(pool, [5, 1, 6], data)
        assert all(tier_of(p) == PINNED_HOST for p in pool.pages)
        untouched = [s for s in range(8) if s not in (5, 1, 6)]
        assert all(pool.pages[s] is before[s] for s in untouched)
        got = ex.read_pages(pool, [6, 5])
        assert tier_of(got) == DEVICE
        np.testing.assert_array_equal(np.asarray(got),
                                      np.asarray(data)[[2, 0]])
        np.testing.assert_array_equal(np.asarray(ex.read_page(pool, 1)),
                                      np.asarray(data[1]))
        assert moved == [3 * page_bytes, 2 * page_bytes, page_bytes]
        # onboard (device) pools are not link traffic
        onboard = ex.alloc_pool(4, (4, 3), jnp.bfloat16, tier="onboard")
        onboard = ex.write_pages(onboard, [0, 2], data[:2])
        assert tier_of(onboard) == DEVICE and len(moved) == 3
