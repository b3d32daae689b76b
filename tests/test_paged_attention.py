"""Ragged paged-attention kernel: interpret-mode shape pins + the
decode-vs-pure-JAX-twin differential (the lane-pad pattern
applied to the generation plane's kernel — interpret-green is not
lowerable-green, so the static 8x128 gate runs on every shape the
decoder will emit)."""

import numpy as np
import pytest


def _rand_case(b, h, p, dp, n_pages, max_pages, seed):
    rng = np.random.default_rng(seed)
    q = rng.normal(size=(b, h, dp)).astype(np.float32)
    k = rng.normal(size=(n_pages, h, p, dp)).astype(np.float32)
    v = rng.normal(size=(n_pages, h, p, dp)).astype(np.float32)
    pt = rng.integers(1, n_pages, size=(b, max_pages)).astype(np.int32)
    sl = rng.integers(0, max_pages * p + 1, size=(b,)).astype(np.int32)
    return q, k, v, pt, sl


@pytest.mark.parametrize(
    "b,h,p,dp,n_pages,max_pages",
    [
        (4, 4, 8, 128, 16, 3),  # the decoder's default layout
        (1, 2, 16, 256, 8, 4),  # multi-lane head_dim
        (8, 4, 8, 128, 32, 5),
        (3, 1, 4, 128, 7, 2),  # page_size below the sublane width
    ],
)
def test_kernel_matches_twin_ragged(b, h, p, dp, n_pages, max_pages):
    """The Pallas kernel (interpret mode) and the jitted pure-JAX twin
    agree over ragged page counts — including zero-length (padded
    batch) slots, which must come back exactly zero."""
    from pathway_tpu.ops import paged_attention as pa

    q, k, v, pt, sl = _rand_case(b, h, p, dp, n_pages, max_pages, b * 31)
    sl[0] = 0  # always include an empty slot
    scale = 1.0 / np.sqrt(32.0)
    ref = np.asarray(
        pa.paged_attention_ref(q, k, v, pt, sl, sm_scale=scale)
    )
    out = np.asarray(
        pa.paged_attention(q, k, v, pt, sl, sm_scale=scale, interpret=True)
    )
    assert np.allclose(ref, out, atol=2e-6), np.abs(ref - out).max()
    assert (out[0] == 0.0).all()  # empty slot zero-fills
    pa.validate_lowering(b, h, p, dp, n_pages, max_pages)


def test_ragged_boundary_lengths():
    """Sequence lengths at the exact page boundaries (0, P, P+1, full)
    mask precisely: equality with a dense masked-softmax oracle."""
    from pathway_tpu.ops import paged_attention as pa

    b, h, p, dp, n_pages, max_pages = 4, 2, 8, 128, 12, 3
    q, k, v, pt, sl = _rand_case(b, h, p, dp, n_pages, max_pages, 99)
    sl[:] = [0, p, p + 1, max_pages * p]
    out = np.asarray(
        pa.paged_attention(q, k, v, pt, sl, sm_scale=0.2, interpret=True)
    )
    # dense oracle in numpy
    for i in range(b):
        n = int(sl[i])
        if n == 0:
            assert (out[i] == 0.0).all()
            continue
        kk = np.concatenate(
            [k[pt[i, j]] for j in range(max_pages)], axis=1
        )[:, :n]  # [H, n, Dp]
        vv = np.concatenate(
            [v[pt[i, j]] for j in range(max_pages)], axis=1
        )[:, :n]
        s = np.einsum("hd,hnd->hn", q[i], kk) * 0.2
        w = np.exp(s - s.max(axis=1, keepdims=True))
        w /= w.sum(axis=1, keepdims=True)
        o = np.einsum("hn,hnd->hd", w, vv)
        assert np.allclose(o, out[i], atol=2e-5)


def test_lane_pad_boundaries():
    """The lane ladder's edges, applied to head_dim."""
    from pathway_tpu.ops.paged_attention import lane_pad

    assert lane_pad(1) == 128
    assert lane_pad(32) == 128  # the decoder default's pad
    assert lane_pad(128) == 128  # aligned: pads to itself
    assert lane_pad(129) == 256  # one past: a full lane width


def test_lowering_gate_rejects_unpadded_head_dim():
    """The 8x128 rule statically: an UNpadded head_dim (passes in interpret
    mode, fails at Mosaic lowering)
    must be rejected by the gate even on the CPU backend."""
    from pathway_tpu.ops import paged_attention as pa

    # decoder shapes that must lower
    pa.validate_lowering(8, 4, 16, 128, 64, 16)
    pa.validate_lowering(1, 1, 8, 256, 4, 2)
    # raw head_dim 32: not a lane multiple
    with pytest.raises(ValueError, match="lane-padded"):
        pa.validate_lowering(8, 4, 16, 32, 64, 16)
    # and the shared rule checker still rejects a bad block outright
    from pathway_tpu.analysis.lowering import check_tpu_block_rules

    with pytest.raises(ValueError):
        check_tpu_block_rules((1, 4, 7, 128), (16, 4, 16, 128))


def test_decode_step_pallas_vs_ref_twin():
    """The full decode step through the Pallas kernel (interpret) and
    through the pure-JAX twin produce the same logits AND the same
    KV-pool contents — the kernel can serve as a drop-in on TPU."""
    import jax.numpy as jnp

    from pathway_tpu.xpacks.llm import decoder as dec

    cfg = dec.DecoderConfig(
        dim=64, n_layers=1, n_heads=2, head_dim=32, ffn_dim=128,
        max_len=64, page_size=8,
    )
    params = dec.init_params(cfg, seed=3)
    toks = dec.encode_text("paged")
    outs = {}
    pools = {}
    for kernel in ("ref", "pallas"):
        k_pool, v_pool = dec.empty_pools(cfg, n_pages=6)
        pt = np.zeros((1, cfg.max_pages), np.int32)
        pt[0, :3] = [1, 2, 3]
        logits_seq = []
        for i, t in enumerate(toks + [65, 66]):
            logits, k_pool, v_pool = dec.decode_step(
                params,
                np.array([t], np.int32),
                np.array([i], np.int32),
                k_pool,
                v_pool,
                jnp.asarray(pt),
                np.array([i + 1], np.int32),
                cfg=cfg,
                kernel=kernel,
            )
            logits_seq.append(np.asarray(logits)[0])
        outs[kernel] = np.stack(logits_seq)
        pools[kernel] = (np.asarray(k_pool), np.asarray(v_pool))
    assert np.allclose(outs["ref"], outs["pallas"], atol=1e-4), np.abs(
        outs["ref"] - outs["pallas"]
    ).max()
    for a, b in zip(pools["ref"], pools["pallas"]):
        assert np.allclose(a, b, atol=1e-4)


def test_twin_page_table_indirection():
    """Two different page tables naming the same physical content give
    identical outputs — the attention depends on the mapped pages, not
    their physical ids (the restore-path invariant: a restored pool
    with different page ids reproduces the run)."""
    from pathway_tpu.ops import paged_attention as pa

    b, h, p, dp, n_pages, max_pages = 2, 2, 8, 128, 10, 2
    q, k, v, pt, sl = _rand_case(b, h, p, dp, n_pages, max_pages, 5)
    sl[:] = [11, 13]
    out1 = np.asarray(pa.paged_attention_ref(q, k, v, pt, sl, sm_scale=1.0))
    # permute physical pages, remap the table accordingly
    perm = np.random.default_rng(6).permutation(n_pages)
    inv = np.argsort(perm)
    k2, v2 = k[perm], v[perm]
    pt2 = inv[pt].astype(np.int32)
    out2 = np.asarray(
        pa.paged_attention_ref(q, k2, v2, pt2, sl, sm_scale=1.0)
    )
    assert np.allclose(out1, out2, atol=1e-6)
