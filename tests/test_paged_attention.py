"""Stash-resident paged-attention kernel tests (ISSUE 4).

Three layers of evidence:

  1. differential — the Pallas kernel (TPU-semantics interpreter on CPU)
     matches the gather-then-dense oracle within fp tolerance across deterministic
     sweeps and hypothesis-random block tables (holes, pool-block reuse,
     n_valid in {0, 1, C}, sliding window on/off, block_size in {8, 16});
  2. acceptance — the compiled paged serve step carries no
     ``(slots, max_blocks*block_size, K, D)`` logical-KV buffer under
     ``kernel="pallas"`` (it does under ``"ref"``), and the modeled HBM
     KV bytes-read per decode step drop >= 4x at <= 25% pool occupancy;
  3. policy — ``resolve_kernel`` auto semantics.
"""
import numpy as np
import pytest

import jax
import jax.experimental.pallas.tpu as pltpu
import jax.numpy as jnp

from repro import compat
from repro.kernels.paged_attention import (modeled_hbm_bytes, paged_attention,
                                           paged_attention_ref,
                                           resolve_kernel)
from repro.kernels.paged_attention.kernel import paged_attention_pallas
from repro.launch.hlo_cost import has_buffer_shape

TOL = dict(atol=5e-5, rtol=5e-5)
BF16_TOL = dict(atol=3e-2, rtol=3e-2)


def _assert_valid_close(y, yr, n_valid, **tol):
    """Compare only columns < n_valid — the step contract: columns beyond
    n_valid are discarded garbage, and on fully-masked rows (seq_end == 0)
    the two paths legitimately diverge (the kernel's l=0 floor yields zeros;
    the dense softmax over an all-NEG_INF row degenerates to a uniform
    average of pool rows)."""
    valid = (np.arange(y.shape[1])[None, :] < np.asarray(n_valid)[:, None])
    valid = valid[:, :, None, None]
    np.testing.assert_allclose(np.where(valid, np.asarray(y, np.float32), 0),
                               np.where(valid, np.asarray(yr, np.float32), 0),
                               **tol)


def _case(rng, *, bs, B, C, K, G, D, M, window, n_valid_choices=(0, 1, None),
          holes=True, dtype=jnp.float32):
    """Random paged-attention inputs with table holes and pool-block reuse."""
    N = B * M + 2
    H = K * G
    q = jnp.asarray(rng.normal(size=(B, C, H, D)) * 0.3, dtype)
    k_pool = jnp.asarray(rng.normal(size=(N, bs, K, D)) * 0.3, dtype)
    v_pool = jnp.asarray(rng.normal(size=(N, bs, K, D)) * 0.3, dtype)
    tables = rng.integers(0, N, size=(B, M)).astype(np.int32)  # reuse allowed
    n_valid = np.asarray([int(rng.choice([c if c is not None else C
                                          for c in n_valid_choices]))
                          for _ in range(B)], np.int32)
    starts = np.asarray([int(rng.integers(0, M * bs - C + 1))
                         for _ in range(B)], np.int32)
    if holes:
        for b in range(B):
            live = -(-(starts[b] + n_valid[b]) // bs)
            tables[b, live:] = -1
    return (q, k_pool, v_pool, jnp.asarray(tables), jnp.asarray(starts),
            jnp.asarray(n_valid))


@pytest.mark.parametrize("bs,B,C,K,G,D,M,window", [
    (8, 2, 4, 2, 2, 16, 3, None),      # chunked prefill, GQA
    (8, 3, 1, 1, 4, 32, 2, None),      # decode rows, MQA-style grouping
    (16, 2, 4, 2, 1, 16, 4, None),     # big blocks, no grouping
    (16, 2, 4, 2, 2, 16, 3, 12),       # sliding window < block
    (8, 2, 1, 1, 1, 16, 4, 20),        # window spanning blocks, decode
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_kernel_matches_ref_sweep(bs, B, C, K, G, D, M, window, dtype):
    rng = np.random.default_rng(hash((bs, B, C, K, G, D, M, window or 0))
                                % 2**32)
    args = _case(rng, bs=bs, B=B, C=C, K=K, G=G, D=D, M=M, window=window,
                 dtype=dtype)
    y = paged_attention(*args, block_size=bs, window=window)
    yr = paged_attention_ref(*args, block_size=bs, window=window)
    assert y.shape == yr.shape and y.dtype == yr.dtype
    _assert_valid_close(y, yr, args[5],
                        **(BF16_TOL if dtype == jnp.bfloat16 else TOL))


def test_window_far_past_start_matches_ref():
    """Decode deep into a sequence with a small sliding window: most live
    blocks sit entirely before the window, exercising the kv index map's
    lower clamp (those steps re-address the first in-window block so the
    pipeline skips their copies) — the result must still match the oracle."""
    rng = np.random.default_rng(13)
    bs, B, C, K, G, D, M = 8, 2, 1, 2, 2, 16, 6
    q, kp, vp, tables, _, _ = _case(rng, bs=bs, B=B, C=C, K=K, G=G, D=D, M=M,
                                    window=None, holes=False)
    starts = jnp.asarray([M * bs - 1, M * bs - 2], jnp.int32)  # deep decode
    n_valid = jnp.ones((B,), jnp.int32)
    for window in (5, bs, 2 * bs + 3):
        y = paged_attention(q, kp, vp, tables, starts, n_valid,
                            block_size=bs, window=window)
        yr = paged_attention_ref(q, kp, vp, tables, starts, n_valid,
                                 block_size=bs, window=window)
        _assert_valid_close(y, yr, n_valid, **TOL)


def test_idle_rows_finite():
    """n_valid == 0 everywhere: zero live blocks, output must be finite."""
    rng = np.random.default_rng(7)
    q, kp, vp, tables, _, _ = _case(rng, bs=8, B=2, C=4, K=2, G=2, D=16, M=2,
                                    window=None)
    zeros = jnp.zeros((2,), jnp.int32)
    y = paged_attention(q, kp, vp, tables, zeros, zeros, block_size=8)
    assert bool(jnp.all(jnp.isfinite(y)))
    np.testing.assert_allclose(np.asarray(y), 0.0, atol=1e-6)


def _hyp():
    return pytest.importorskip(
        "hypothesis", reason="hypothesis not installed")


def test_kernel_matches_ref_property():
    """Hypothesis: random geometry, tables with holes/reuse, n_valid in
    {0, 1, C}, window on/off, block_size in {8, 16}."""
    hyp = _hyp()
    from hypothesis import given, settings, strategies as st

    @settings(max_examples=25, deadline=None)
    @given(st.data())
    def run(data):
        bs = data.draw(st.sampled_from([8, 16]), label="block_size")
        B = data.draw(st.integers(1, 3), label="B")
        C = data.draw(st.sampled_from([1, 4]), label="C")
        K = data.draw(st.sampled_from([1, 2]), label="K")
        G = data.draw(st.sampled_from([1, 2]), label="G")
        D = data.draw(st.sampled_from([8, 16]), label="D")
        M = data.draw(st.integers(2, 4), label="M")
        window = data.draw(
            st.one_of(st.none(), st.integers(2, 2 * bs)), label="window")
        seed = data.draw(st.integers(0, 2**31 - 1), label="seed")
        holes = data.draw(st.booleans(), label="holes")
        rng = np.random.default_rng(seed)
        args = _case(rng, bs=bs, B=B, C=C, K=K, G=G, D=D, M=M, window=window,
                     holes=holes)
        y = paged_attention(*args, block_size=bs, window=window)
        yr = paged_attention_ref(*args, block_size=bs, window=window)
        _assert_valid_close(y, yr, args[5], **TOL)

    run()


def test_kernel_under_tpu_semantics_interpreter():
    """The CI paged-kernel job's target: the same differential check, run
    under the TPU-semantics interpreter (exercises SMEM scalar prefetch and
    the pipelined pool DMAs with mosaic rules, not generic-interpret ones).
    """
    rng = np.random.default_rng(11)
    args = _case(rng, bs=8, B=2, C=4, K=2, G=2, D=16, M=3, window=None)
    y = paged_attention_pallas(*args, block_size=8, window=None,
                               interpret=pltpu.InterpretParams())
    yr = paged_attention_ref(*args, block_size=8, window=None)
    _assert_valid_close(y, yr, args[5], **TOL)


# ---------------------------------------------------------------------------
# acceptance: no logical-KV materialization + modeled bytes reduction
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def paged_step_hlo():
    """Compiled paged serve step HLO under both kernels (smoke model)."""
    from repro.configs.base import SHAPES, RunConfig, ShardingConfig
    from repro.configs.registry import get_smoke
    from repro.runtime.steps import make_paged_serve_step

    cfg = get_smoke("llama3.2-1b")
    run = RunConfig(model=cfg, shape=SHAPES["decode_32k"],
                    sharding=ShardingConfig(fsdp_params=False, seq_axis=None))
    mesh = compat.make_mesh((1, 1), ("data", "model"))
    geom = dict(slots=3, chunk=4, num_blocks=16, block_size=4,
                max_blocks_per_seq=8)
    texts = {}
    with mesh:
        for kern in ("ref", "pallas"):
            b = make_paged_serve_step(cfg, run, mesh, kernel=kern, **geom)
            assert b.meta["paged_kernel"] == kern
            texts[kern] = (jax.jit(b.fn, in_shardings=b.in_shardings,
                                   out_shardings=b.out_shardings)
                           .lower(*b.abstract_inputs).compile().as_text())
    return cfg, geom, texts


def test_hlo_no_logical_kv_materialization(paged_step_hlo):
    """ISSUE 4 acceptance: the (slots, max_blocks*block_size, K, D) logical
    view exists in the ref step's HLO and is GONE from the pallas step's."""
    cfg, geom, texts = paged_step_hlo
    a = cfg.attention
    dense = (geom["slots"], geom["max_blocks_per_seq"] * geom["block_size"],
             a.num_kv_heads, a.head_dim)
    assert has_buffer_shape(texts["ref"], dense), \
        "oracle step lost its materialization — the check is vacuous"
    assert not has_buffer_shape(texts["pallas"], dense), \
        f"pallas step still materializes the logical KV view {dense}"


def test_modeled_bytes_reduction_at_quarter_occupancy():
    """>= 4x modeled HBM KV bytes-read reduction at <= 25% pool occupancy.

    Re-derived for the bounded ref model (ISSUE 7 satellite): the ref path
    gathers every slot to the block-rounded LONGEST resident length (the
    ``max_resident`` bound, not the full table capacity) and pays it twice
    (materialize + read), so its bytes scale with ``B * t_max``. The
    pallas path reads each request's own live blocks exactly once. With
    uniform lengths the two lengths coincide and ref's only waste is the
    double pass (~2x); the >=4x claim at low occupancy comes from length
    *skew* — one straggler pins ``t_max`` for every slot while the short
    rows cost the kernel a single block each."""
    for bs in (8, 16):
        max_blocks, B = 8, 8
        for frac in (0.5, 1.0):            # straggler at half / full length
            lens = [int(frac * max_blocks * bs)] + [1] * (B - 1)
            kw = dict(block_size=bs, max_blocks=max_blocks, kv_heads=2,
                      head_dim=64)
            occ = sum(-(-s // bs) for s in lens) / (B * max_blocks)
            assert occ <= 0.25, (bs, frac, occ)
            ref = modeled_hbm_bytes(lens, kernel="ref", **kw)
            pal = modeled_hbm_bytes(lens, kernel="pallas", **kw)
            assert ref / pal >= 4.0, (bs, frac, ref, pal)
    # uniform lengths: exactly the double-pass factor and nothing more —
    # the old model charged ref the full table capacity regardless of
    # residency, inflating the ratio the benchmark then failed to measure
    kw = dict(block_size=8, max_blocks=8, kv_heads=2, head_dim=64)
    assert (modeled_hbm_bytes([16] * 4, kernel="ref", **kw)
            == 2 * modeled_hbm_bytes([16] * 4, kernel="pallas", **kw))


# ---------------------------------------------------------------------------
# policy
# ---------------------------------------------------------------------------

def test_resolve_kernel_policy():
    """auto resolves to the Pallas kernel (compiled on TPU, interpreted
    elsewhere) and is device-count independent — the sharded lowering serves every mesh size, so
    n_devices never demotes pallas to ref (ISSUE 7)."""
    for n in (1, 4, 64):
        assert resolve_kernel("auto", n_devices=n) == "pallas"
        assert resolve_kernel("pallas", n_devices=n) == "pallas"
        assert resolve_kernel("ref", n_devices=n) == "ref"
    assert resolve_kernel("auto") == "pallas"     # n_devices defaults to 1
    with pytest.raises(ValueError, match="kernel must be one of"):
        resolve_kernel("nope")


def test_gather_max_resident_bound():
    """Satellite: gather(seq_lens=) returns the block-rounded live bound."""
    from repro.models.kvcache import PagedKVCache
    cache = PagedKVCache.init(num_blocks=6, block_size=4, kv_heads=1,
                              head_dim=8)
    tables = jnp.asarray([[0, 1, -1], [2, 3, 4]], jnp.int32)
    k, v, max_res = cache.gather(tables, seq_lens=jnp.asarray([3, 9]))
    assert k.shape == (2, 12, 1, 8) and v.shape == (2, 12, 1, 8)
    assert int(max_res) == 12                   # ceil(9/4)*4
    k2, v2, max_res2 = cache.gather(tables, seq_lens=jnp.asarray([1, 2]))
    assert int(max_res2) == 4
    # two-arg form unchanged
    k3, v3 = cache.gather(tables)
    np.testing.assert_array_equal(np.asarray(k3), np.asarray(k))
