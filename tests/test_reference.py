"""The plain float32 reference forward (``repro.models.reference``) and the
served path's logits against it, at the smoke size of llama3.2-1b.

The reference is written apart from ``models/``; it must agree with the
repo's own forward run in float32 to float32 rounding, and the paged
engine's bf16 logits (Pallas kernel, interpreted here) must agree with it
within the bounds ``chip_smoke.py`` applies at full width on the chip.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import compat
from repro.configs.registry import get_smoke
from repro.engine import Request
from repro.engine.teacher_forcing import teacher_forced_logits
from repro.launch.serve import build_engine
from repro.models import model as model_lib
from repro.models import reference

RMS_TOL = 0.05          # rms(served - ref) / rms(ref), as in chip_smoke.py
MAX_TOL = 0.25          # max|served - ref| / std(ref)


@pytest.fixture(scope="module")
def served():
    cfg = get_smoke("llama3.2-1b")
    mesh = compat.make_mesh((1, 1), ("data", "model"))
    rng = np.random.default_rng(5)
    prompts = [rng.integers(0, cfg.vocab_size, size=(n,)).astype(np.int32)
               for n in (19, 11)]
    with mesh:
        eng = build_engine(cfg, mesh, cache="paged", slots=3, max_len=32,
                           num_blocks=16, block_size=4, chunk=4)
        eng.load_params()
        for rid, p in enumerate(prompts):
            eng.submit(Request(rid, p, max_new_tokens=5))
        done = {r.rid: r for r in eng.run_until_drained()}
        seqs = [[int(t) for t in prompts[i]] + done[i].out_tokens[:-1]
                for i in range(2)]
        logits = teacher_forced_logits(eng, seqs, [len(p) for p in prompts])
    return cfg, eng, prompts, done, seqs, logits


def test_reference_matches_model_forward_in_float32(served):
    cfg, eng, _, _, seqs, _ = served
    tokens = jnp.asarray([seqs[0]])
    ref = reference.forward_f32(cfg, eng.params, tokens)
    p32 = jax.tree.map(lambda t: t.astype(jnp.float32), eng.params)
    with jax.default_matmul_precision("highest"):
        got, _, _ = model_lib.forward(cfg, p32, tokens,
                                      compute_dtype=jnp.float32)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), atol=1e-4,
                               rtol=1e-4)


@pytest.mark.parametrize("i", [0, 1])
def test_served_logits_match_reference(served, i):
    """Prefill and decode logits of the paged step, fed as the engine fed
    them, agree with the reference; their argmax is the served token."""
    cfg, eng, prompts, done, seqs, logits = served
    assert logits[i].shape == (len(seqs[i]), cfg.vocab_size)
    ref = np.asarray(reference.forward_f32(cfg, eng.params,
                                           jnp.asarray([seqs[i]]))[0])
    diff = logits[i] - ref
    assert np.sqrt(np.mean(diff ** 2) / np.mean(ref ** 2)) <= RMS_TOL
    assert np.abs(diff).max() / ref.std() <= MAX_TOL
    L = len(prompts[i])
    assert list(logits[i][L - 1:].argmax(-1)) == done[i].out_tokens


def test_teacher_forcing_runs_what_does_not_fit_in_groups(served):
    """More sequences than the engine has slots run in groups; every
    sequence's logits are those it gets when forced on its own."""
    _, eng, prompts, _, seqs, logits = served
    lens = [len(p) for p in prompts]
    with eng.mesh:
        grouped = teacher_forced_logits(eng, seqs * 2, lens * 2)
    assert len(grouped) == 4
    for i, got in enumerate(grouped):
        np.testing.assert_array_equal(got, logits[i % 2])


def test_reference_refuses_unsupported_stacks():
    cfg = get_smoke("mamba-130m")
    assert not reference.supports(cfg)
    with pytest.raises(ValueError, match="dense GQA"):
        reference.forward_f32(cfg, {}, jnp.zeros((1, 4), jnp.int32))
