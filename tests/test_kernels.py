"""Pallas kernel sweeps vs the ref.py oracles (interpret mode on CPU)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.kernels.flash_attention.ops import (flash_attention,
                                               flash_decode_attention)
from repro.kernels.flash_attention.ref import attention_ref
from repro.models.attention import attention_decode
from repro.kernels.rglru.ops import rglru_scan
from repro.kernels.rglru.ref import rglru_scan_ref


def _fa_case(b, sq, sk, hq, hkv, dh, dt, causal, window, softcap,
             qb=64, kb=64, seed=0):
    key = jax.random.PRNGKey(seed)
    ks = jax.random.split(key, 3)
    q = jax.random.normal(ks[0], (b, sq, hq, dh), dt)
    k = jax.random.normal(ks[1], (b, sk, hkv, dh), dt)
    v = jax.random.normal(ks[2], (b, sk, hkv, dh), dt)
    out = flash_attention(q, k, v, causal=causal, window=window,
                          softcap=softcap, q_block=qb, kv_block=kb,
                          interpret=True)
    qh = q.transpose(0, 2, 1, 3).reshape(b * hq, sq, dh)
    kh = k.transpose(0, 2, 1, 3).reshape(b * hkv, sk, dh)
    vh = v.transpose(0, 2, 1, 3).reshape(b * hkv, sk, dh)
    ref = attention_ref(qh, kh, vh, causal=causal, window=window,
                        softcap=softcap)
    ref = ref.reshape(b, hq, sq, dh).transpose(0, 2, 1, 3)
    tol = 2.5e-2 if dt == jnp.bfloat16 else 3e-5
    err = float(np.max(np.abs(np.asarray(out, np.float32)
                              - np.asarray(ref, np.float32))))
    assert err < tol, (err, tol)


# shape sweep: batch/seq/head/group/dh grid
@pytest.mark.parametrize("b,sq,hq,hkv,dh", [
    (1, 128, 2, 2, 16), (2, 128, 4, 2, 32), (1, 256, 6, 2, 64),
    (2, 64, 5, 1, 16), (1, 128, 8, 8, 8),
])
def test_flash_shapes(b, sq, hq, hkv, dh):
    _fa_case(b, sq, sq, hq, hkv, dh, jnp.float32, True, 0, 0.0)


@pytest.mark.parametrize("dt", [jnp.float32, jnp.bfloat16])
def test_flash_dtypes(dt):
    _fa_case(1, 128, 128, 4, 2, 32, dt, True, 0, 0.0)


@pytest.mark.parametrize("window", [16, 64])
def test_flash_local_window(window):
    _fa_case(1, 128, 128, 2, 1, 16, jnp.float32, True, window, 0.0)


def test_flash_non_causal():
    _fa_case(1, 64, 128, 2, 2, 16, jnp.float32, False, 0, 0.0)


def test_flash_softcap():
    _fa_case(1, 128, 128, 2, 2, 16, jnp.float32, True, 0, 10.0)


@settings(max_examples=8, deadline=None)
@given(seed=st.integers(0, 1000),
       qb=st.sampled_from([32, 64]), kb=st.sampled_from([32, 64, 128]))
def test_flash_block_shape_invariance(seed, qb, kb):
    """Output must not depend on the BlockSpec tiling."""
    _fa_case(1, 128, 128, 2, 2, 16, jnp.float32, True, 0, 0.0,
             qb=qb, kb=kb, seed=seed)


# ---------------- ragged decode kernel ----------------

def _ragged_case(b, smax, hq, hkv, dh, kb, softcap=0.0, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    q = jax.random.normal(ks[0], (b, 1, hq, dh), jnp.float32)
    k = jax.random.normal(ks[1], (b, smax, hkv, dh), jnp.float32)
    v = jax.random.normal(ks[2], (b, smax, hkv, dh), jnp.float32)
    idx = jax.random.randint(ks[3], (b,), 0, smax)
    out = flash_decode_attention(q, k, v, idx, softcap=softcap,
                                 kv_block=kb, interpret=True)
    ref = attention_decode(q, k, v, idx, softcap=softcap)
    err = float(np.max(np.abs(np.asarray(out) - np.asarray(ref))))
    assert err < 3e-5, err


@pytest.mark.parametrize("b,smax,hq,hkv,dh,kb", [
    (3, 128, 4, 2, 16, 32), (2, 256, 6, 2, 32, 64), (4, 64, 5, 1, 16, 64),
    (1, 128, 8, 8, 8, 128),
])
def test_ragged_decode_shapes(b, smax, hq, hkv, dh, kb):
    """Per-slot cache lengths (continuous batching) vs the model-side
    vector-index attention_decode oracle."""
    _ragged_case(b, smax, hq, hkv, dh, kb)


def test_ragged_decode_softcap():
    _ragged_case(2, 128, 4, 2, 16, 32, softcap=10.0)


@pytest.mark.parametrize("arch,max_len", [
    ("qwen2-0.5b", 64),        # GQA + qkv bias
    ("smollm-360m", 48),       # max_len not a multiple of the kv block
])
def test_model_decode_step_ragged_kernel_matches_oracle(arch, max_len):
    """Model.decode_step(use_ragged_kernel=True) routes per-slot decode
    attention through the Pallas kernel (interpret mode on CPU) and must
    match the jnp attention_decode path bit-for-bit on logits AND cache
    — including idx 0 (fresh slot) and idx at the cache edge."""
    from repro.configs import get_smoke_config
    from repro.models.model import Model
    cfg = get_smoke_config(arch)
    m = Model(cfg)
    params = m.init(jax.random.PRNGKey(0))
    cache = m.init_cache(4, max_len, per_slot=True)
    key = jax.random.PRNGKey(1)
    cache["stack"] = jax.tree.map(
        lambda a: jax.random.normal(key, a.shape, a.dtype)
        if jnp.issubdtype(a.dtype, jnp.floating) else a, cache["stack"])
    cache["idx"] = jnp.asarray([0, 3, max_len // 2, max_len - 2],
                               jnp.int32)
    tok = jnp.asarray([3, 7, 11, 2], jnp.int32)
    ref_logits, ref_cache = m.decode_step(params, cache, tokens=tok)
    out_logits, out_cache = m.decode_step(params, cache, tokens=tok,
                                          use_ragged_kernel=True)
    np.testing.assert_allclose(np.asarray(out_logits),
                               np.asarray(ref_logits), rtol=2e-5,
                               atol=2e-5)
    for a, b in zip(jax.tree.leaves(ref_cache), jax.tree.leaves(out_cache)):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32),
                                   rtol=2e-5, atol=2e-5)


def test_continuous_engine_ragged_kernel_same_tokens():
    """The continuous engine produces identical tokens with the ragged
    kernel on and off (the flag changes the data path, not the math)."""
    from repro.configs import get_smoke_config
    from repro.models.model import Model
    from repro.serve.engine import ContinuousEngine, Request
    cfg = get_smoke_config("qwen2-0.5b")
    params = Model(cfg).init(jax.random.PRNGKey(0))

    def serve(flag):
        eng = ContinuousEngine(cfg, params, n_slots=2, max_len=64,
                               use_ragged_kernel=flag)
        for i, (plen, new) in enumerate([(8, 4), (16, 6), (12, 3)]):
            eng.submit(Request(
                rid=i, prompt=np.arange(1, 1 + plen, dtype=np.int32),
                max_new_tokens=new))
        return {r.rid: r.output for r in eng.run()}

    assert serve(False) == serve(True)


def test_decode_program_text_is_the_engine_decode_step():
    """``decode_program_text`` compiles the decode executable for the
    engine's live cache without disturbing it: serving afterwards gives
    the same tokens as an engine that never asked."""
    from repro.configs import get_smoke_config
    from repro.models.model import Model
    from repro.serve.engine import ContinuousEngine, Request
    cfg = get_smoke_config("qwen2-0.5b")
    params = Model(cfg).init(jax.random.PRNGKey(0))

    def serve(inspect):
        eng = ContinuousEngine(cfg, params, n_slots=2, max_len=64,
                               decode_horizon=4, use_ragged_kernel=True)
        if inspect:
            assert "ENTRY" in eng.decode_program_text()
        eng.submit(Request(rid=0, prompt=np.arange(1, 9, dtype=np.int32),
                           max_new_tokens=6))
        return eng.run()[0].output

    assert serve(True) == serve(False)


def test_ragged_kernel_refuses_max_len_it_cannot_take():
    """A prime max_len above 256 leaves the kernel no kv block it can
    take: the model step and the engine raise, naming max_len, instead
    of serving jnp attention in silence.  Without the kernel any
    max_len serves."""
    from repro.configs import get_smoke_config
    from repro.models.model import Model
    from repro.models.transformer import ragged_kv_block
    from repro.serve.engine import ContinuousEngine
    assert ragged_kv_block(512) == 256 and ragged_kv_block(48) == 48
    cfg = get_smoke_config("qwen2-0.5b")
    m = Model(cfg)
    params = m.init(jax.random.PRNGKey(0))
    cache = m.init_cache(2, 257, per_slot=True)
    tok = jnp.asarray([1, 2], jnp.int32)
    with pytest.raises(ValueError, match="max_len=257"):
        m.decode_step(params, cache, tokens=tok, use_ragged_kernel=True)
    with pytest.raises(ValueError, match="max_len=257"):
        ContinuousEngine(cfg, params, n_slots=2, max_len=257,
                         use_ragged_kernel=True)
    ContinuousEngine(cfg, params, n_slots=2, max_len=257)


def test_kernel_wrappers_interpret_only_on_the_cpu(monkeypatch):
    """The TPU compiles the kernels, the CPU interprets them, and any
    other backend is refused rather than interpreted in silence."""
    from repro.kernels import mode
    assert mode.interpret_mode() is (jax.default_backend() == "cpu")
    monkeypatch.setattr(mode.jax, "default_backend", lambda: "tpu")
    assert mode.interpret_mode() is False
    monkeypatch.setattr(mode.jax, "default_backend", lambda: "gpu")
    with pytest.raises(RuntimeError, match="'gpu'"):
        mode.interpret_mode()


def test_ragged_decode_block_invariance():
    ks = jax.random.split(jax.random.PRNGKey(3), 4)
    q = jax.random.normal(ks[0], (2, 1, 4, 16), jnp.float32)
    k = jax.random.normal(ks[1], (2, 128, 2, 16), jnp.float32)
    v = jax.random.normal(ks[2], (2, 128, 2, 16), jnp.float32)
    idx = jnp.asarray([5, 100], jnp.int32)
    outs = [np.asarray(flash_decode_attention(q, k, v, idx, kv_block=kb,
                                              interpret=True))
            for kb in (32, 64, 128)]
    for o in outs[1:]:
        np.testing.assert_allclose(o, outs[0], rtol=1e-6, atol=1e-6)


# ---------------- RG-LRU kernel ----------------

@pytest.mark.parametrize("b,t,c,tb,cb", [
    (1, 128, 64, 32, 32), (2, 256, 128, 64, 64), (1, 64, 256, 64, 128),
    (3, 128, 64, 128, 64),
])
def test_rglru_shapes(b, t, c, tb, cb):
    key = jax.random.PRNGKey(0)
    a = jax.nn.sigmoid(jax.random.normal(key, (b, t, c)))
    x = jax.random.normal(jax.random.fold_in(key, 1), (b, t, c))
    out = rglru_scan(a, x, t_block=tb, c_block=cb, interpret=True)
    ref = rglru_scan_ref(a, x)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("dt,tol", [(jnp.float32, 1e-5),
                                    (jnp.bfloat16, 4e-2)])
def test_rglru_dtypes(dt, tol):
    key = jax.random.PRNGKey(1)
    a = jax.nn.sigmoid(jax.random.normal(key, (2, 128, 64))).astype(dt)
    x = jax.random.normal(jax.random.fold_in(key, 1), (2, 128, 64), dt)
    out = rglru_scan(a, x, t_block=64, c_block=64, interpret=True)
    ref = rglru_scan_ref(a, x)
    err = float(np.max(np.abs(np.asarray(out, np.float32)
                              - np.asarray(ref, np.float32))))
    assert err < tol


def test_rglru_block_invariance():
    key = jax.random.PRNGKey(2)
    a = jax.nn.sigmoid(jax.random.normal(key, (1, 128, 128)))
    x = jax.random.normal(jax.random.fold_in(key, 1), (1, 128, 128))
    outs = [np.asarray(rglru_scan(a, x, t_block=tb, c_block=cb,
                                  interpret=True))
            for tb, cb in [(32, 32), (64, 128), (128, 64)]]
    for o in outs[1:]:
        np.testing.assert_allclose(o, outs[0], rtol=1e-6, atol=1e-6)


def test_rglru_matches_model_oracle():
    """Kernel output == the model-side associative scan used in
    models/recurrent.py (same recurrence, independent code paths)."""
    from repro.configs import get_smoke_config
    from repro.models import params as P
    from repro.models.recurrent import _rglru_gates, rglru_specs
    cfg = get_smoke_config("recurrentgemma-2b")
    p = P.materialize(rglru_specs(cfg), jax.random.PRNGKey(0))
    u = jax.random.normal(jax.random.PRNGKey(1), (2, 64, cfg.lru_width))
    a, x_in = _rglru_gates(p, u)
    from repro.models.recurrent import rglru_scan as model_scan
    h_model = model_scan(p, u)
    h_kernel = rglru_scan(a, x_in, t_block=32, c_block=32, interpret=True)
    np.testing.assert_allclose(np.asarray(h_kernel), np.asarray(h_model),
                               rtol=2e-4, atol=2e-4)


# ---------------- paged decode kernel ----------------

from repro.kernels.flash_attention.ops import paged_flash_decode_attention
from repro.models.attention import attention_decode_paged, gather_pages


def _paged_tables(key, b, max_pages, n_pages, mapped):
    """Disjoint, scrambled page tables: slot i owns ``mapped[i]`` pages
    drawn from one random permutation of the pool (fragmented physical
    layout), sentinel-padded to ``max_pages``."""
    perm = np.asarray(jax.random.permutation(key, n_pages))
    pt = np.full((b, max_pages), n_pages, np.int32)
    at = 0
    for i, m in enumerate(mapped):
        pt[i, :m] = perm[at:at + m]
        at += m
    return jnp.asarray(pt)


def _paged_case(b, max_len, ps, hq, hkv, dh, softcap=0.0, seed=0,
                n_pages=None, idx=None):
    max_pages = max_len // ps
    if n_pages is None:
        n_pages = b * max_pages
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    q = jax.random.normal(ks[0], (b, 1, hq, dh), jnp.float32)
    kp = jax.random.normal(ks[1], (n_pages, ps, hkv, dh), jnp.float32)
    vp = jax.random.normal(ks[2], (n_pages, ps, hkv, dh), jnp.float32)
    if idx is None:
        idx = jax.random.randint(ks[3], (b,), 0, max_len)
    idx = jnp.asarray(idx, jnp.int32)
    # map exactly the pages each slot's history reaches (ragged)
    mapped = [-(-(int(i) + 1) // ps) for i in np.asarray(idx)]
    pt = _paged_tables(ks[4], b, max_pages, n_pages, mapped)
    out = paged_flash_decode_attention(q, kp, vp, pt, idx,
                                       softcap=softcap, interpret=True)
    ref = attention_decode_paged(q, kp, vp, pt, idx, page_size=ps,
                                 max_len=max_len, softcap=softcap)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=3e-5, atol=3e-5)


@pytest.mark.parametrize("b,max_len,ps,hq,hkv,dh", [
    (3, 64, 16, 4, 2, 16),      # GQA, 4 pages/slot
    (2, 128, 32, 6, 2, 32),     # wider heads
    (4, 64, 8, 5, 1, 16),       # MQA, 8 pages/slot
    (1, 64, 64, 8, 8, 8),       # single-page degenerate (== contiguous)
])
def test_paged_decode_shapes(b, max_len, ps, hq, hkv, dh):
    """Pallas paged gather kernel vs the jnp page-gather oracle over
    fragmented tables and ragged lengths."""
    _paged_case(b, max_len, ps, hq, hkv, dh)


def test_paged_decode_softcap():
    _paged_case(2, 64, 16, 4, 2, 16, softcap=10.0)


def test_paged_decode_edge_lengths():
    """idx 0 (fresh slot, one mapped page), the page boundary, and the
    cache edge — the @pl.when skip must drop exactly the unmapped tail."""
    _paged_case(4, 64, 16, 4, 2, 16, idx=[0, 15, 16, 63])


def test_paged_decode_tight_pool():
    """Pool far smaller than b * max_pages (the whole point of pooling):
    slots' mapped pages interleave in one shared physical array."""
    _paged_case(4, 64, 8, 4, 2, 16, n_pages=14, idx=[7, 20, 1, 15])


@settings(max_examples=8, deadline=None)
@given(seed=st.integers(0, 1000), ps=st.sampled_from([8, 16, 32]))
def test_paged_decode_page_size_sweep(seed, ps):
    _paged_case(2, 64, ps, 4, 2, 16, seed=seed)


def test_paged_decode_fragmentation_invariance():
    """The SAME logical cache through an identity table and a scrambled
    one must produce bit-identical outputs — physical placement is
    invisible to the math."""
    b, max_len, ps, hq, hkv, dh = 2, 64, 16, 4, 2, 16
    max_pages = max_len // ps
    n = b * max_pages
    ks = jax.random.split(jax.random.PRNGKey(7), 4)
    q = jax.random.normal(ks[0], (b, 1, hq, dh), jnp.float32)
    kp = jax.random.normal(ks[1], (n, ps, hkv, dh), jnp.float32)
    vp = jax.random.normal(ks[2], (n, ps, hkv, dh), jnp.float32)
    idx = jnp.asarray([30, 63], jnp.int32)
    pt_id = jnp.arange(n, dtype=jnp.int32).reshape(b, max_pages)
    perm = np.asarray(jax.random.permutation(ks[3], n))
    pt_sc = jnp.asarray(perm[np.asarray(pt_id)])
    inv = np.argsort(perm)
    kp_sc, vp_sc = kp[inv], vp[inv]     # page perm[p] holds old page p
    a = paged_flash_decode_attention(q, kp, vp, pt_id, idx, interpret=True)
    c = paged_flash_decode_attention(q, kp_sc, vp_sc, pt_sc, idx,
                                     interpret=True)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(c))


def test_paged_gather_oracle_matches_contiguous():
    """attention_decode_paged == attention_decode on the materialized
    contiguous view — the paged path inherits contiguous numerics."""
    b, max_len, ps, hq, hkv, dh = 3, 64, 16, 4, 2, 16
    ks = jax.random.split(jax.random.PRNGKey(9), 4)
    q = jax.random.normal(ks[0], (b, 1, hq, dh), jnp.float32)
    n = b * (max_len // ps)
    kp = jax.random.normal(ks[1], (n, ps, hkv, dh), jnp.float32)
    vp = jax.random.normal(ks[2], (n, ps, hkv, dh), jnp.float32)
    idx = jnp.asarray([0, 17, 63], jnp.int32)
    pt = _paged_tables(ks[3], b, max_len // ps, n, [1, 2, 4])
    ref = attention_decode_paged(q, kp, vp, pt, idx, page_size=ps,
                                 max_len=max_len)
    kg = gather_pages(kp, pt, ps, max_len)
    vg = gather_pages(vp, pt, ps, max_len)
    np.testing.assert_array_equal(
        np.asarray(ref), np.asarray(attention_decode(q, kg, vg, idx)))


@pytest.mark.parametrize("arch", ["qwen2-0.5b", "smollm-360m"])
def test_model_decode_step_paged_matches_contiguous(arch):
    """Model.decode_step over the paged cache (fragmented tables, ragged
    per-slot lengths) produces bit-identical logits to the contiguous
    cache across multiple steps — so paged WRITES land in the right
    pages (later steps attend to rows written by earlier ones)."""
    from repro.configs import get_smoke_config
    from repro.models.model import Model
    cfg = get_smoke_config(arch)
    m = Model(cfg)
    if not m.supports_paged_cache:
        pytest.skip(f"{arch}: no paged cache")
    params = m.init(jax.random.PRNGKey(0))
    b, max_len, ps = 3, 32, 8
    n_pages = b * (max_len // ps)
    ccache = m.init_cache(b, max_len, per_slot=True)
    pcache = m.init_cache(b, max_len, per_slot=True, page_size=ps,
                          n_pages=n_pages)
    idx = jnp.asarray([0, 5, 19], jnp.int32)
    ccache["idx"] = idx
    pcache["idx"] = idx
    pcache["pt"] = _paged_tables(jax.random.PRNGKey(4), b,
                                 max_len // ps, n_pages, [4, 4, 4])
    for t in range(4):
        tok = jnp.asarray([3 + t, 7, 11 * (t + 1) % 50], jnp.int32)
        clog, ccache = m.decode_step(params, ccache, tokens=tok)
        plog, pcache = m.decode_step(params, pcache, tokens=tok)
        np.testing.assert_array_equal(np.asarray(clog), np.asarray(plog))
    np.testing.assert_array_equal(np.asarray(ccache["idx"]),
                                  np.asarray(pcache["idx"]))
