"""Mailbox Pallas kernel tests: remote DMA needs >1 device -> subprocess.

Covers: ring put (WFE + poll waits), stash-fused Server-Side Sum, non-stash
HBM drain, Indirect Put with GOT indirection — each against ref.py.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tests.helpers import run_multidev

from repro.core.message import FrameSpec, pack_frame
from repro.kernels.mailbox import am_indirect_put, am_server_sum
from repro.kernels.mailbox.ref import indirect_put_ref, server_sum_ref

SPEC = FrameSpec(got_slots=4, state_words=0, payload_words=16)


def _frames(n, seed=0):
    key = jax.random.PRNGKey(seed)
    payloads = jax.random.randint(key, (n, SPEC.payload_words), 0, 100,
                                  jnp.int32)
    return jnp.stack([pack_frame(SPEC, func_id=0, payload_words=payloads[i])
                      for i in range(n)])


# -- single-device handler kernels (no subprocess needed) ---------------------

def test_server_sum_kernel_matches_ref():
    frames = _frames(6)
    got = am_server_sum(frames, SPEC)
    want = server_sum_ref(frames, SPEC.offsets()["usr"], SPEC.payload_words)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("n", [1, 7, 127, 130])
def test_server_sum_awkward_frame_counts(n):
    """Prime / non-dividing N must pad up to one tile multiple, not degrade
    the grid to width-1 tiles (ISSUE 4 satellite) — and stay exact."""
    from repro.kernels.mailbox.kernel import _drain_geometry
    frames = _frames(n, seed=n)
    got = am_server_sum(frames, SPEC)
    want = server_sum_ref(frames, SPEC.offsets()["usr"], SPEC.payload_words)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    bn, n_pad = _drain_geometry(n, 128)
    assert n_pad % bn == 0 and n_pad >= n
    assert bn >= 8 and bn % 8 == 0, (n, bn)   # never a width-1 tile


def test_drain_geometry_cases():
    from repro.kernels.mailbox.kernel import _drain_geometry
    assert _drain_geometry(127, 128) == (128, 128)   # the prime-N headline
    assert _drain_geometry(4, 128) == (8, 8)
    assert _drain_geometry(130, 128) == (128, 256)
    # caller-passed non-multiple-of-8 tile rounds down to stay aligned
    assert _drain_geometry(127, 100) == (96, 192)


def test_indirect_put_kernel_matches_ref():
    frames = _frames(5, seed=3)
    slots = 8
    table = jnp.zeros((slots, 2), jnp.int32)
    heap = jnp.zeros((slots, SPEC.payload_words - 1), jnp.int32)
    for got_base in (0, 3):
        got = jnp.asarray([got_base, 0, 0, 0], jnp.int32)
        t_k, h_k = am_indirect_put(frames, table, heap, got, SPEC)
        t_r, h_r = indirect_put_ref(frames, table, heap,
                                    SPEC.offsets()["usr"],
                                    SPEC.payload_words, got_base)
        np.testing.assert_array_equal(np.asarray(t_k), np.asarray(t_r))
        np.testing.assert_array_equal(np.asarray(h_k), np.asarray(h_r))


def test_indirect_put_last_writer_wins():
    """Two frames with colliding keys: the later frame's payload lands."""
    slots = 4
    p1 = jnp.asarray([5] + [1] * (SPEC.payload_words - 1), jnp.int32)
    p2 = jnp.asarray([5 + slots] + [2] * (SPEC.payload_words - 1), jnp.int32)
    frames = jnp.stack([pack_frame(SPEC, func_id=0, payload_words=p)
                        for p in (p1, p2)])
    table = jnp.zeros((slots, 2), jnp.int32)
    heap = jnp.zeros((slots, SPEC.payload_words - 1), jnp.int32)
    got = jnp.zeros((4,), jnp.int32)
    _, h = am_indirect_put(frames, table, heap, got, SPEC)
    np.testing.assert_array_equal(np.asarray(h[(5 + slots) % slots]),
                                  np.full(SPEC.payload_words - 1, 2))


# -- multi-device remote-DMA paths ------------------------------------------

_MULTIDEV = r"""
import jax, jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh
from repro.core.message import FrameSpec, pack_frame
from repro.kernels.mailbox import ring_am_put, am_server_sum
from repro.kernels.mailbox.ref import ring_put_ref, server_sum_ref

spec = FrameSpec(got_slots=4, state_words=0, payload_words=16)
o = spec.offsets()
n_ranks, N = 4, 3
key = jax.random.PRNGKey(0)
payloads = jax.random.randint(key, (n_ranks, N, spec.payload_words), 0, 100, jnp.int32)
frames = jnp.stack([jnp.stack([pack_frame(spec, func_id=0, payload_words=payloads[r, i])
                    for i in range(N)]) for r in range(n_ranks)])
mesh = Mesh(np.array(jax.devices()).reshape(4), ("x",))
ref = ring_put_ref(frames)

arr, spins, _ = ring_am_put(frames, mesh, "x", spec=spec, wait="wfe", stash=True)
assert (np.asarray(arr) == np.asarray(ref)).all(), "wfe arrivals"
assert (np.asarray(spins) == 0).all(), "wfe must not spin"

arr2, spins2, _ = ring_am_put(frames, mesh, "x", spec=spec, wait="poll", stash=True)
assert (np.asarray(arr2) == np.asarray(ref)).all(), "poll arrivals"
assert (np.asarray(spins2) >= 1).all(), "poll must count spins"

arr3, _, sums = ring_am_put(frames, mesh, "x", spec=spec, wait="wfe",
                            stash=True, handler="sum")
want = np.stack([np.asarray(server_sum_ref(ref[r], o["usr"], spec.payload_words))
                 for r in range(n_ranks)])
assert (np.asarray(sums)[..., 0] == want).all(), "fused stash sums"

arr4, _, _ = ring_am_put(frames, mesh, "x", spec=spec, wait="wfe", stash=False)
assert (np.asarray(arr4) == np.asarray(ref)).all(), "non-stash arrivals"
sums4 = jax.vmap(lambda f: am_server_sum(f, spec))(arr4)
assert (np.asarray(sums4) == want).all(), "non-stash drained sums"

# shift=2 ring (multi-hop addressing)
arr5, _, _ = ring_am_put(frames, mesh, "x", spec=spec, shift=2)
assert (np.asarray(arr5) == np.asarray(ring_put_ref(frames, 2))).all(), "shift2"
print("MAILBOX_MULTIDEV_OK")
"""


def test_mailbox_remote_dma_multidev():
    out = run_multidev(_MULTIDEV, n_devices=4)
    assert "MAILBOX_MULTIDEV_OK" in out
