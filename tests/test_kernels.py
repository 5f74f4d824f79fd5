"""Pallas kernel sweeps: shapes x dtypes against the ref.py oracles
(interpret=True on CPU)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ref
from repro.kernels.eh_lookup import (eh_lookup, sharded_eh_lookup,
                                     sharded_shortcut_lookup,
                                     shortcut_lookup)
from repro.kernels.flash_attention import flash_attention
from repro.kernels.paged_attention import paged_attention
from repro.kernels.ragged_copy import ragged_copy
from repro.kernels.shortcut_attention import shortcut_attention

from conftest import unique_keys


def tol(dtype):
    return dict(rtol=2e-2, atol=2e-2) if dtype == jnp.bfloat16 \
        else dict(rtol=2e-5, atol=2e-5)


class TestFlashAttention:
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    @pytest.mark.parametrize(
        "B,KV,G,Sq,hd,bq,bkv",
        [(1, 1, 1, 64, 16, 16, 32),
         (2, 2, 4, 128, 32, 32, 64),
         (1, 4, 2, 96, 64, 32, 32),    # ragged: 96 % 64 != 0
         (2, 1, 8, 128, 128, 64, 128)])
    def test_causal_sweep(self, dtype, B, KV, G, Sq, hd, bq, bkv):
        ks = jax.random.split(jax.random.PRNGKey(0), 3)
        q = jax.random.normal(ks[0], (B, KV, G, Sq, hd), dtype)
        k = jax.random.normal(ks[1], (B, KV, Sq, hd), dtype)
        v = jax.random.normal(ks[2], (B, KV, Sq, hd), dtype)
        out = flash_attention(q, k, v, bq=bq, bkv=bkv)
        want = ref.flash_attention_ref(q, k, v)
        np.testing.assert_allclose(
            np.asarray(out, np.float32), np.asarray(want, np.float32),
            **tol(dtype))

    @pytest.mark.parametrize("window", [16, 33, 100])
    def test_sliding_window(self, window):
        ks = jax.random.split(jax.random.PRNGKey(1), 3)
        q = jax.random.normal(ks[0], (1, 2, 2, 128, 32), jnp.float32)
        k = jax.random.normal(ks[1], (1, 2, 128, 32), jnp.float32)
        v = jax.random.normal(ks[2], (1, 2, 128, 32), jnp.float32)
        out = flash_attention(q, k, v, bq=32, bkv=32, window=window)
        want = ref.flash_attention_ref(q, k, v, window=window)
        np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                                   rtol=2e-5, atol=2e-5)

    def test_softcap(self):
        ks = jax.random.split(jax.random.PRNGKey(2), 3)
        q = jax.random.normal(ks[0], (1, 1, 2, 64, 32), jnp.float32) * 3
        k = jax.random.normal(ks[1], (1, 1, 64, 32), jnp.float32) * 3
        v = jax.random.normal(ks[2], (1, 1, 64, 32), jnp.float32)
        out = flash_attention(q, k, v, bq=32, bkv=32, softcap=20.0)
        want = ref.flash_attention_ref(q, k, v, softcap=20.0)
        np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                                   rtol=2e-5, atol=2e-5)

    def test_prefill_shorter_q(self):
        """Right-aligned q against a longer kv (chunked prefill shape)."""
        ks = jax.random.split(jax.random.PRNGKey(3), 3)
        q = jax.random.normal(ks[0], (1, 2, 2, 32, 32), jnp.float32)
        k = jax.random.normal(ks[1], (1, 2, 128, 32), jnp.float32)
        v = jax.random.normal(ks[2], (1, 2, 128, 32), jnp.float32)
        out = flash_attention(q, k, v, bq=32, bkv=32)
        want = ref.flash_attention_ref(q, k, v)
        np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                                   rtol=2e-5, atol=2e-5)


class TestDecodeKernels:
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    @pytest.mark.parametrize("B,KV,G,hd,S,bs",
                             [(2, 1, 4, 32, 64, 16),
                              (3, 2, 2, 64, 96, 32),
                              (1, 4, 1, 128, 128, 128)])
    def test_shortcut_sweep(self, dtype, B, KV, G, hd, S, bs):
        ks = jax.random.split(jax.random.PRNGKey(4), 3)
        q = jax.random.normal(ks[0], (B, KV, G, hd), dtype)
        kv = jax.random.normal(ks[1], (2, B, KV, S, hd), dtype)
        ctx = jnp.asarray(
            np.random.default_rng(0).integers(1, S + 1, B), jnp.int32)
        out = shortcut_attention(q, kv[0], kv[1], ctx, bs=bs)
        want = ref.decode_attention_ref(q, kv[0], kv[1], ctx)
        np.testing.assert_allclose(
            np.asarray(out, np.float32), np.asarray(want, np.float32),
            **tol(dtype))

    def test_shortcut_window(self):
        ks = jax.random.split(jax.random.PRNGKey(5), 3)
        q = jax.random.normal(ks[0], (2, 2, 2, 32), jnp.float32)
        kv = jax.random.normal(ks[1], (2, 2, 2, 96, 32), jnp.float32)
        ctx = jnp.asarray([96, 41], jnp.int32)
        out = shortcut_attention(q, kv[0], kv[1], ctx, bs=32, window=17)
        want = ref.decode_attention_ref(q, kv[0], kv[1], ctx, window=17)
        np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                                   rtol=2e-5, atol=2e-5)

    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    @pytest.mark.parametrize("B,KV,G,hd,bs,nb,MB",
                             [(2, 2, 2, 32, 16, 24, 6),
                              (3, 1, 4, 64, 8, 48, 8)])
    def test_paged_sweep(self, dtype, B, KV, G, hd, bs, nb, MB):
        ks = jax.random.split(jax.random.PRNGKey(6), 3)
        q = jax.random.normal(ks[0], (B, KV, G, hd), dtype)
        kp = jax.random.normal(ks[1], (nb, KV, bs, hd), dtype)
        vp = jax.random.normal(ks[2], (nb, KV, bs, hd), dtype)
        rng = np.random.default_rng(1)
        tables = np.full((B, MB), -1, np.int32)
        lens = rng.integers(1, MB * bs + 1, B).astype(np.int32)
        pool = list(rng.permutation(nb))
        for b in range(B):
            for m in range(-(-int(lens[b]) // bs)):
                tables[b, m] = pool.pop()
        out = paged_attention(q, kp, vp, jnp.asarray(tables),
                              jnp.asarray(lens))
        want = ref.paged_attention_ref(q, kp, vp, jnp.asarray(tables),
                                       jnp.asarray(lens))
        np.testing.assert_allclose(
            np.asarray(out, np.float32), np.asarray(want, np.float32),
            **tol(dtype))

    def test_paged_equals_shortcut_when_linear(self):
        """Identity block table => both paths must agree exactly (the
        paper's Figure 1 equivalence)."""
        B, KV, G, hd, bs, MB = 2, 2, 2, 32, 8, 6
        ks = jax.random.split(jax.random.PRNGKey(7), 3)
        q = jax.random.normal(ks[0], (B, KV, G, hd), jnp.float32)
        kp = jax.random.normal(ks[1], (B * MB, KV, bs, hd), jnp.float32)
        vp = jax.random.normal(ks[2], (B * MB, KV, bs, hd), jnp.float32)
        tables = jnp.arange(B * MB, dtype=jnp.int32).reshape(B, MB)
        lens = jnp.asarray([MB * bs, 3 * bs + 2], jnp.int32)
        paged = paged_attention(q, kp, vp, tables, lens)
        # pool (B*MB, KV, bs, hd) -> contiguous view (B, KV, MB*bs, hd)
        view = kp.reshape(B, MB, KV, bs, hd).transpose(
            0, 2, 1, 3, 4).reshape(B, KV, MB * bs, hd)
        view_v = vp.reshape(B, MB, KV, bs, hd).transpose(
            0, 2, 1, 3, 4).reshape(B, KV, MB * bs, hd)
        short = shortcut_attention(q, view, view_v, lens, bs=bs)
        np.testing.assert_allclose(np.asarray(paged), np.asarray(short),
                                   rtol=1e-6, atol=1e-6)


class TestEHKernels:
    @pytest.mark.parametrize("n,slots,tile", [(200, 16, 128),
                                              (1000, 8, 256)])
    def test_lookup_sweep(self, rng, n, slots, tile):
        from repro.core import extendible_hashing as eh
        keys = unique_keys(rng, n)
        st = eh.eh_create(max_global_depth=9, bucket_slots=slots,
                          capacity=1024)
        st, _, _ = eh.eh_insert_many(
            st, jnp.asarray(keys), jnp.asarray(np.arange(n, dtype=np.uint32)))
        D = 1 << int(st.global_depth)
        probe = np.concatenate(
            [keys, unique_keys(rng, 100, lo=2**31, hi=2**32 - 2)])
        out = eh_lookup(jnp.asarray(probe), st.directory[:D],
                        st.bucket_keys, st.bucket_vals, st.global_depth,
                        tile=tile)
        want = ref.eh_lookup_ref(jnp.asarray(probe), st.directory[:D],
                                 st.bucket_keys, st.bucket_vals,
                                 st.global_depth)
        np.testing.assert_array_equal(np.asarray(out), np.asarray(want))

    @pytest.mark.parametrize("N", [1, 4])
    def test_sharded_kernel_matches_per_shard(self, rng, N):
        """One grid-over-shards dispatch == N independent single-shard
        calls (the shard loop shares one pallas_call specialization)."""
        from repro.core import extendible_hashing as eh
        states, probes = [], []
        for s in range(N):
            keys = unique_keys(rng, 150 + 40 * s)
            st = eh.eh_create(max_global_depth=8, bucket_slots=8,
                              capacity=256)
            st, _, _ = eh.eh_insert_many(
                st, jnp.asarray(keys),
                jnp.asarray(np.arange(keys.size, dtype=np.uint32)))
            states.append(st)
            probes.append(np.concatenate(
                [keys, unique_keys(rng, 50, lo=2**31, hi=2**32 - 2)]))
        K = max(p.size for p in probes)
        padded = np.zeros((N, K), np.uint32)
        for s, p in enumerate(probes):
            padded[s, :p.size] = p
        out = sharded_eh_lookup(
            jnp.asarray(padded),
            jnp.stack([st.directory for st in states]),
            jnp.stack([st.bucket_keys for st in states]),
            jnp.stack([st.bucket_vals for st in states]),
            jnp.asarray([int(st.global_depth) for st in states],
                        jnp.int32), tile=128)
        D = states[0].directory.shape[0]
        for s, st in enumerate(states):
            want = eh_lookup(jnp.asarray(padded[s]), st.directory[:D],
                             st.bucket_keys, st.bucket_vals,
                             st.global_depth, tile=128)
            np.testing.assert_array_equal(np.asarray(out[s]),
                                          np.asarray(want))
        # shortcut flavour over shape-uniform composed views
        V = 1 << max(int(st.global_depth) for st in states)
        views = [eh.compose_shortcut(st, V) for st in states]
        out_sc = sharded_shortcut_lookup(
            jnp.asarray(padded),
            jnp.stack([vk for vk, _ in views]),
            jnp.stack([vv for _, vv in views]),
            jnp.asarray([int(st.global_depth) for st in states],
                        jnp.int32), tile=128)
        for s, st in enumerate(states):
            want = shortcut_lookup(jnp.asarray(padded[s]), *views[s],
                                   st.global_depth, tile=128)
            np.testing.assert_array_equal(np.asarray(out_sc[s]),
                                          np.asarray(want))

    @pytest.mark.parametrize("slots", [8, 64])
    def test_rank_mask_probe_matches_gather_probe(self, rng, slots):
        """``hashing.probe_rows`` (the kernels' rank-mask probe) answers
        as the XLA path's gather probe does on adversarial rows: ghost
        hits past an EMPTY, repeated keys, full rows and misses."""
        from repro.core import hashing
        T = 512
        keys = rng.integers(0, 6, T, dtype=np.uint32)   # tiny alphabet
        row_k = rng.integers(0, 6, (T, slots), dtype=np.uint32)
        row_k[rng.random((T, slots)) < 0.15] = hashing.EMPTY_SENTINEL
        row_v = rng.integers(0, 2**32, (T, slots), dtype=np.uint32)

        def gather_probe(rk, rv, key):
            pos = hashing.probe_positions(key, slots)
            found, j = hashing.probe_hit(rk[pos], key)
            return found, jnp.where(found, rv[pos[j]], jnp.uint32(0))

        want_f, want_v = jax.vmap(gather_probe)(
            jnp.asarray(row_k), jnp.asarray(row_v), jnp.asarray(keys))
        found, value = hashing.probe_rows(
            jnp.asarray(row_k), jnp.asarray(row_v),
            jnp.asarray(keys)[:, None])
        np.testing.assert_array_equal(np.asarray(found[:, 0]),
                                      np.asarray(want_f))
        np.testing.assert_array_equal(np.asarray(value[:, 0]),
                                      np.asarray(want_v))
        assert 0 < int(want_f.sum()) < T       # both outcomes exercised

    def test_shortcut_kernel_matches_traditional(self, rng):
        from repro.core import extendible_hashing as eh
        keys = unique_keys(rng, 500)
        st = eh.eh_create(max_global_depth=8, bucket_slots=16,
                          capacity=512)
        st, _, _ = eh.eh_insert_many(
            st, jnp.asarray(keys),
            jnp.asarray(np.arange(500, dtype=np.uint32)))
        D = 1 << int(st.global_depth)
        vk, vv = eh.compose_shortcut(st, D)
        probe = jnp.asarray(keys)
        trad = eh_lookup(probe, st.directory[:D], st.bucket_keys,
                         st.bucket_vals, st.global_depth, tile=128)
        short = shortcut_lookup(probe, vk, vv, st.global_depth, tile=128)
        np.testing.assert_array_equal(np.asarray(trad), np.asarray(short))


class TestRaggedCopy:
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16,
                                       jnp.uint32])
    @pytest.mark.parametrize("row", [(8,), (4, 6)])
    def test_sweep(self, rng, dtype, row):
        view = jnp.asarray(
            rng.normal(size=(20,) + row).astype(np.float32)).astype(dtype)
        pool = jnp.asarray(
            rng.normal(size=(40,) + row).astype(np.float32)).astype(dtype)
        slots = jnp.asarray(rng.choice(20, 7, replace=False), jnp.int32)
        offs = jnp.asarray(rng.choice(40, 7), jnp.int32)
        out = ragged_copy(view, pool, slots, offs)
        want = ref.ragged_copy_ref(view, pool, slots, offs)
        np.testing.assert_array_equal(np.asarray(out), np.asarray(want))
