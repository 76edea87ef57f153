"""Pallas kernels vs pure-jnp oracles (interpret mode, shape/dtype sweeps)."""
import jax
import jax.numpy as jnp
import pytest

from repro.kernels.flash_attention.kernel import (
    FIRST, LAST, MASKED, VMEM_BUDGET, _block_pairs, choose_blocks,
    flash_attention, vmem_bytes)
from repro.kernels.flash_attention.ref import attention_ref
from repro.kernels.rglru.ops import rglru_scan
from repro.kernels.rglru.ref import rglru_ref
from repro.kernels.rmsnorm.ops import rmsnorm
from repro.kernels.rmsnorm.ref import rmsnorm_ref

pytestmark = pytest.mark.compile   # whole module drives XLA compiles

RNG = jax.random.PRNGKey(0)


def rand(key, shape, dtype=jnp.float32):
    return jax.random.normal(key, shape).astype(dtype)


class TestFlashAttention:
    @pytest.mark.parametrize("b,sq,h,kvh,dh,causal,window", [
        (2, 256, 4, 2, 64, True, 0),      # GQA causal
        (1, 128, 4, 4, 32, True, 0),      # MHA
        (2, 256, 4, 1, 64, True, 64),     # MQA + sliding window
        (1, 512, 2, 2, 128, False, 0),    # bidirectional
        (1, 256, 8, 2, 128, True, 128),   # GQA + window
    ])
    def test_matches_ref(self, b, sq, h, kvh, dh, causal, window):
        ks = jax.random.split(jax.random.PRNGKey(hash((b, sq, h)) % 2**31), 3)
        q = rand(ks[0], (b, sq, h, dh))
        k = rand(ks[1], (b, sq, kvh, dh))
        v = rand(ks[2], (b, sq, kvh, dh))
        out = flash_attention(q, k, v, causal=causal, window=window,
                              block_q=128, block_k=128, interpret=True)
        ref = attention_ref(q, k, v, causal=causal, window=window)
        assert jnp.max(jnp.abs(out - ref)) < 2e-5

    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_dtypes(self, dtype):
        ks = jax.random.split(RNG, 3)
        q = rand(ks[0], (1, 128, 2, 64), dtype)
        k = rand(ks[1], (1, 128, 2, 64), dtype)
        v = rand(ks[2], (1, 128, 2, 64), dtype)
        out = flash_attention(q, k, v, interpret=True, block_q=128,
                              block_k=128)
        ref = attention_ref(q, k, v)
        tol = 2e-5 if dtype == jnp.float32 else 2e-2
        assert out.dtype == dtype
        assert jnp.max(jnp.abs(out.astype(jnp.float32)
                               - ref.astype(jnp.float32))) < tol

    def test_block_size_independence(self):
        ks = jax.random.split(RNG, 3)
        q = rand(ks[0], (1, 256, 2, 32))
        k = rand(ks[1], (1, 256, 2, 32))
        v = rand(ks[2], (1, 256, 2, 32))
        o1 = flash_attention(q, k, v, block_q=64, block_k=64, interpret=True)
        o2 = flash_attention(q, k, v, block_q=128, block_k=256,
                             interpret=True)
        assert jnp.max(jnp.abs(o1 - o2)) < 2e-5

    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    @pytest.mark.parametrize("sq,skv,window,q_offset,block_q,block_k", [
        # causal: pairs wholly inside, straddling and skipped, Bq != Bk
        (512, 512, 0, 0, 128, 256),
        # prefill continuation: q_offset > 0, sq < skv
        (256, 512, 0, 256, 128, 128),
        # a window whose edge falls inside a block
        (512, 512, 200, 0, 128, 256),
        # ... one key inside a block below the diagonal
        (512, 512, 255, 0, 128, 128),
        # the chooser's own blocks at prefill-b4's and decode-b32's prompts
        (3584, 3584, 0, 0, None, None),
        (512, 512, 0, 0, None, None),
    ])
    def test_block_pairs_match_ref(self, dtype, sq, skv, window, q_offset,
                                   block_q, block_k):
        ks = jax.random.split(jax.random.PRNGKey(sq + window + q_offset), 3)
        q = rand(ks[0], (1, sq, 2, 64), dtype)
        k = rand(ks[1], (1, skv, 1, 64), dtype)
        v = rand(ks[2], (1, skv, 1, 64), dtype)
        out = flash_attention(q, k, v, window=window, q_offset=q_offset,
                              block_q=block_q, block_k=block_k,
                              interpret=True)
        ref = attention_ref(q, k, v, window=window, q_offset=q_offset)
        tol = 2e-5 if dtype == jnp.float32 else 2e-2
        assert out.dtype == dtype
        assert jnp.max(jnp.abs(out.astype(jnp.float32)
                               - ref.astype(jnp.float32))) < tol

    def test_causal_grid_lists_only_needed_pairs(self):
        """prefill-b4's 3,584 positions in 512-blocks: 28 of the 49 pairs
        take a grid step, and only the 7 on the diagonal build a mask."""
        qb, kb, flags = _block_pairs(7, 7, 512, 512, True, 0, 0)
        assert len(qb) == 28 and (kb <= qb).all()
        assert ((flags & MASKED) != 0).sum() == 7
        assert (kb[(flags & MASKED) != 0] == qb[(flags & MASKED) != 0]).all()
        assert (kb[(flags & FIRST) != 0] == 0).all()
        assert (kb[(flags & LAST) != 0] == range(7)).all()

    @pytest.mark.parametrize("sq,skv,dh,dtype,want", [
        (3584, 3584, 64, jnp.bfloat16, (512, 512)),   # prefill-b4
        (512, 512, 64, jnp.bfloat16, (512, 512)),     # decode-b32's prefill
        (384, 384, 64, jnp.bfloat16, (128, 128)),
        (1024, 4096, 128, jnp.float32, None),
        (512, 8192, 256, jnp.float32, None),
    ])
    def test_chosen_blocks_divide_and_fit(self, sq, skv, dh, dtype, want):
        bq, bk = choose_blocks(sq, skv, dh, dtype)
        assert sq % bq == 0 and skv % bk == 0
        assert vmem_bytes(bq, bk, dh, jnp.dtype(dtype).itemsize) <= VMEM_BUDGET
        assert want is None or (bq, bk) == want

    def test_indivisible_length_takes_whole_sequence(self):
        assert choose_blocks(1000, 1000, 64, jnp.float32) == (1000, 1000)
        ks = jax.random.split(RNG, 3)
        q, k, v = (rand(kk, (1, 1000, 2, 64)) for kk in ks)
        out = flash_attention(q, k, v, interpret=True)
        assert jnp.max(jnp.abs(out - attention_ref(q, k, v))) < 2e-5

    @pytest.mark.parametrize("sq,block_q,want", [
        (512, None, 128),     # the chooser's 512-row blocks
        (1000, None, 1000),   # no 128 divides: one whole-sequence chunk
        (256, 64, 64),        # explicit blocks under 128 keep theirs
    ])
    def test_backward_chunks_at_most_128_rows(self, monkeypatch, sq,
                                              block_q, want):
        """The backward, the chunked XLA path's VJP, keeps q chunks of at
        most 128 rows whatever the forward's blocks."""
        import repro.models.attention as attention
        chunks, chunked = [], attention.chunked_attention

        def spy(*args, q_chunk, **kw):
            chunks.append(q_chunk)
            return chunked(*args, q_chunk=q_chunk, **kw)

        monkeypatch.setattr(attention, "chunked_attention", spy)
        q = rand(RNG, (1, sq, 1, 8))
        jax.make_jaxpr(jax.grad(lambda q: flash_attention(
            q, q, q, block_q=block_q, interpret=True).sum()))(q)
        assert chunks == [want]

    @pytest.mark.parametrize("sq,h,kvh,window,q_offset", [
        (256, 4, 2, 0, 0),       # GQA causal, two q blocks
        (256, 4, 1, 64, 0),      # MQA + sliding window
        (128, 2, 2, 0, 32),      # prefill continuation offset
    ])
    def test_gradient_matches_xla_path(self, sq, h, kvh, window, q_offset):
        """The kernel's custom VJP equals the XLA chunked path's gradient,
        for q, k and v (GQA: k/v grads summed over each query group)."""
        from repro.models.attention import chunked_attention
        ks = jax.random.split(jax.random.PRNGKey(sq + h + window), 4)
        q = rand(ks[0], (2, sq, h, 32))
        k = rand(ks[1], (2, sq, kvh, 32))
        v = rand(ks[2], (2, sq, kvh, 32))
        w = rand(ks[3], (2, sq, h, 32))

        def loss(attn):
            return lambda q, k, v: jnp.sum(attn(q, k, v) * w)

        kernel = loss(lambda q, k, v: flash_attention(
            q, k, v, window=window, q_offset=q_offset, block_q=128,
            block_k=128, interpret=True))
        xla = loss(lambda q, k, v: chunked_attention(
            q, k, v, window=window, q_offset=q_offset, q_chunk=64))
        got = jax.grad(kernel, argnums=(0, 1, 2))(q, k, v)
        want = jax.grad(xla, argnums=(0, 1, 2))(q, k, v)
        for g, r in zip(got, want):
            assert g.shape == r.shape
            assert jnp.max(jnp.abs(g - r)) < 1e-4

    def test_xla_chunked_path_matches(self):
        from repro.models.attention import chunked_attention
        ks = jax.random.split(RNG, 3)
        q = rand(ks[0], (2, 256, 4, 32))
        k = rand(ks[1], (2, 256, 2, 32))
        v = rand(ks[2], (2, 256, 2, 32))
        out = chunked_attention(q, k, v, q_chunk=64)
        ref = attention_ref(q, k, v)
        assert jnp.max(jnp.abs(out - ref)) < 2e-5


class TestRGLRU:
    @pytest.mark.parametrize("b,s,d", [(2, 64, 128), (1, 256, 256),
                                       (3, 128, 384)])
    def test_pallas_matches_ref(self, b, s, d):
        ks = jax.random.split(jax.random.PRNGKey(d), 3)
        x = rand(ks[0], (b, s, d))
        la = -jax.nn.softplus(rand(ks[1], (b, s, d)))
        h0 = rand(ks[2], (b, d))
        out = rglru_scan(x, la, h0, force="pallas_interpret", seq_chunk=64)
        ref = rglru_ref(x, la, h0)
        assert jnp.max(jnp.abs(out - ref)) < 1e-4

    def test_xla_associative_matches_ref(self):
        ks = jax.random.split(RNG, 3)
        x = rand(ks[0], (2, 128, 64))
        la = -jax.nn.softplus(rand(ks[1], (2, 128, 64)))
        h0 = rand(ks[2], (2, 64))
        out = rglru_scan(x, la, h0, force="xla")
        ref = rglru_ref(x, la, h0)
        assert jnp.max(jnp.abs(out - ref)) < 1e-4

    def test_chunked_state_carry(self):
        """Sequence chunking through h0 must be exact."""
        ks = jax.random.split(RNG, 2)
        x = rand(ks[0], (1, 128, 128))
        la = -jnp.abs(rand(ks[1], (1, 128, 128))) * 0.2
        full = rglru_scan(x, la, force="pallas_interpret", seq_chunk=128)
        chunked = rglru_scan(x, la, force="pallas_interpret", seq_chunk=32)
        assert jnp.max(jnp.abs(full - chunked)) < 1e-5


class TestRMSNorm:
    @pytest.mark.parametrize("shape,dtype", [
        ((4, 64, 128), jnp.float32),
        ((2, 32, 256), jnp.bfloat16),
        ((8, 512), jnp.bfloat16),
        ((16, 8, 384), jnp.float32),
    ])
    def test_matches_ref_exactly(self, shape, dtype):
        ks = jax.random.split(jax.random.PRNGKey(shape[-1]), 2)
        x = rand(ks[0], shape, dtype)
        w = rand(ks[1], shape[-1:], dtype) + 1
        out = rmsnorm(x, w, force="pallas_interpret")
        ref = rmsnorm_ref(x, w)
        assert out.dtype == ref.dtype
        # identical math; <= 1 ulp of fp32 reassociation in the reduce
        assert jnp.max(jnp.abs(out.astype(jnp.float32)
                               - ref.astype(jnp.float32))) < 4e-6


class TestFlashDecode:
    """Single-token decode over a KV cache (the decode_32k hot path)."""

    @pytest.mark.parametrize("b,h,kvh,dh,L,clen,win", [
        (2, 4, 2, 64, 256, 100, 0),     # GQA, partial cache
        (1, 8, 1, 32, 128, 128, 0),     # MQA, full cache
        (2, 4, 4, 64, 256, 200, 64),    # MHA + sliding window
        (1, 2, 2, 128, 512, 37, 0),     # short cache in a long buffer
    ])
    def test_matches_ref(self, b, h, kvh, dh, L, clen, win):
        from repro.kernels.flash_decode.kernel import flash_decode
        from repro.kernels.flash_decode.ref import decode_ref
        ks = jax.random.split(jax.random.PRNGKey(L + clen), 3)
        q = rand(ks[0], (b, h, dh))
        k = rand(ks[1], (b, L, kvh, dh))
        v = rand(ks[2], (b, L, kvh, dh))
        out = flash_decode(q, k, v, jnp.int32(clen), window=win,
                           block_k=min(128, L), interpret=True)
        ref = decode_ref(q, k, v, jnp.int32(clen), window=win)
        assert jnp.max(jnp.abs(out - ref)) < 2e-5

    def test_matches_model_decode_attention(self):
        """Kernel semantics == the model substrate's decode path."""
        from repro.kernels.flash_decode.ref import decode_ref
        from repro.models.attention import decode_attention
        ks = jax.random.split(jax.random.PRNGKey(7), 3)
        q = rand(ks[0], (2, 4, 32))
        k = rand(ks[1], (2, 64, 2, 32))
        v = rand(ks[2], (2, 64, 2, 32))
        a = decode_ref(q, k, v, jnp.int32(40))
        # the model keeps a row per position and attends before it writes
        # the new token's (position 39) own k/v
        rows = [x.reshape(2, 64, 2 * 32) for x in (k, v)]
        bq = decode_attention(q[:, None], *rows, k[:, 39:40], v[:, 39:40],
                              jnp.int32(40))[:, 0]
        assert jnp.max(jnp.abs(a - bq)) < 2e-5

    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_dtypes(self, dtype):
        from repro.kernels.flash_decode.kernel import flash_decode
        from repro.kernels.flash_decode.ref import decode_ref
        ks = jax.random.split(jax.random.PRNGKey(0), 3)
        q = rand(ks[0], (1, 4, 64), dtype)
        k = rand(ks[1], (1, 128, 2, 64), dtype)
        v = rand(ks[2], (1, 128, 2, 64), dtype)
        out = flash_decode(q, k, v, jnp.int32(90), block_k=128,
                           interpret=True)
        ref = decode_ref(q, k, v, jnp.int32(90))
        tol = 2e-5 if dtype == jnp.float32 else 3e-2
        assert out.dtype == dtype
        assert jnp.max(jnp.abs(out.astype(jnp.float32)
                               - ref.astype(jnp.float32))) < tol
