"""Mamba-2 SSD kernels vs the sequential-recurrence oracle: the chunked
scan of prefill, and the one-token decode update of the live slots."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ref
from repro.kernels.ssd_decode import ssd_decode
from repro.kernels.ssd_scan import ssd_scan


def _inputs(rng, b, s, h, p, n, dtype=jnp.float32):
    x = jnp.asarray(rng.standard_normal((b, s, h, p)), dtype)
    dt = jnp.asarray(rng.uniform(0.01, 0.3, (b, s, h)), jnp.float32)
    A = jnp.asarray(-rng.uniform(0.3, 2.0, (h,)), jnp.float32)
    B = jnp.asarray(rng.standard_normal((b, s, n)), dtype)
    C = jnp.asarray(rng.standard_normal((b, s, n)), dtype)
    D = jnp.asarray(rng.standard_normal((h,)), jnp.float32)
    return x, dt, A, B, C, D


@pytest.mark.parametrize("b,s,h,p,n,chunk", [
    (1, 64, 2, 16, 16, 16),
    (2, 128, 3, 32, 64, 32),
    (1, 256, 4, 64, 128, 128),
])
def test_ssd_matches_recurrence(b, s, h, p, n, chunk, rng):
    x, dt, A, B, C, D = _inputs(rng, b, s, h, p, n)
    y_ref, st_ref = ref.ssd_ref(x, dt, A, B, C, D)
    y, st = ssd_scan(x, dt, A, B, C, D, chunk=chunk, interpret=True)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref),
                               rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(np.asarray(st), np.asarray(st_ref),
                               rtol=2e-4, atol=2e-4)


def test_ssd_chunk_invariance(rng):
    """The chunked algorithm must be exactly chunk-size independent."""
    x, dt, A, B, C, D = _inputs(rng, 1, 128, 2, 16, 32)
    y32, st32 = ssd_scan(x, dt, A, B, C, D, chunk=32, interpret=True)
    y64, st64 = ssd_scan(x, dt, A, B, C, D, chunk=64, interpret=True)
    np.testing.assert_allclose(np.asarray(y32), np.asarray(y64),
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(st32), np.asarray(st64),
                               rtol=1e-4, atol=1e-4)


def test_ssd_decode_continues_prefill(rng):
    """Prefill state + one decode step == full sequence at s+1."""
    b, s, h, p, n = 1, 64, 2, 16, 16
    x, dt, A, B, C, D = _inputs(rng, b, s + 1, h, p, n)
    y_full, _ = ref.ssd_ref(x, dt, A, B, C, D)
    _, state = ssd_scan(x[:, :s], dt[:, :s], A, B[:, :s], C[:, :s], D,
                        chunk=32, interpret=True)
    y1, _ = ref.ssd_decode_ref(x[:, s], dt[:, s], A, B[:, s], C[:, s], D,
                               state)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y_full[:, s]),
                               rtol=2e-4, atol=2e-4)


def test_ssd_state_decay(rng):
    """With large dt*|A| the carried state must vanish (no leakage across
    chunks)."""
    b, s, h, p, n = 1, 64, 1, 8, 8
    x, dt, A, B, C, D = _inputs(rng, b, s, h, p, n)
    A_big = jnp.full((h,), -50.0)
    dt_big = jnp.full_like(dt, 5.0)
    _, state = ssd_scan(x, dt_big, A_big, B, C, D, chunk=16, interpret=True)
    # state = sum over j of exp(L_last - L_j) dt B x; only the last step
    # survives: bounded by dt * |B| * |x|
    assert np.isfinite(np.asarray(state)).all()


LIVE = {"all": [1] * 8, "one": [0, 0, 0, 0, 0, 1, 0, 0],
        "scattered": [1, 0, 0, 1, 1, 0, 1, 0], "none": [0] * 8}


@pytest.mark.parametrize("live", LIVE.values(), ids=LIVE.keys())
@pytest.mark.parametrize("h,p,n", [(128, 64, 128), (24, 64, 128)],
                         ids=["granite", "mamba2-130m"])
def test_ssd_decode_updates_live_slots_in_place(h, p, n, live, rng):
    """Eight slots at granite-4.0-h-small's and mamba2-130m's head widths,
    the state stacked over two layers and the second one updated: the
    live slots' y and state match the oracle, a finished slot's state and
    the other layer are left bit for bit, and a finished slot's y is 0."""
    b, layer = len(live), 1
    x, dt, A, B, C, D = (t[:, 0] if t.ndim > 1 else t
                         for t in _inputs(rng, b, 1, h, p, n))
    state = jnp.asarray(rng.standard_normal((2, b, h, p, n)), jnp.float32)
    lv = np.asarray(live, bool)
    y_ref, st_ref = ref.ssd_decode_ref(x, dt, A, B, C, D, state[layer])
    y, st = ssd_decode(x, dt, A, B, C, D, state, layer, jnp.asarray(lv),
                       interpret=True)
    y, st, state = map(np.asarray, (y, st, state))
    np.testing.assert_allclose(y[lv], np.asarray(y_ref)[lv],
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(st[layer][lv], np.asarray(st_ref)[lv],
                               rtol=1e-6, atol=1e-6)
    np.testing.assert_array_equal(st[layer][~lv], state[layer][~lv])
    np.testing.assert_array_equal(st[1 - layer], state[1 - layer])
    assert np.isfinite(y).all() and not y[~lv].any()
