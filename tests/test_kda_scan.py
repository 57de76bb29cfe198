"""The chunked gated delta rule (``pathway_tpu/ops/kda_scan.py``) against the
recurrence it stands for, computed token by token in float64, one document
at a time:

    S_t = (I - beta_t k_t k_t^T) Diag(exp(g_t)) S_{t-1} + beta_t k_t v_t^T,
    o_t = S_t^T q_t.

Over one document longer than several chunks, over a packed axis of
documents whose borders fall mid-chunk and on chunk edges with a padded
tail, with ``beta`` near 0 and near 1, and with decays of 20 nats a token and
more (where ``exp(G_i) exp(-G_j)`` overflows float32 within five tokens).
The XLA form and the Pallas kernel's body in interpret mode, both.

Tolerance: both sides sum the same products in another order, one side in
float32 (the running sum of the decay, the inverse of ``I + A`` by products,
the carried state): ``TOL`` of the largest output (read here: 1e-7 to 3e-6,
the largest with the strongest decays, whose running sums reach thousands).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from pathway_tpu.ops import kda_scan as K

H, DK, DV = 2, 32, 16
TOL = 2e-5


def _inputs(t: int, seed: int, decay: float = 1.0, beta=None):
    """Unit ``q`` and ``k`` a head (the model L2-norms them), log-decays of
    ``decay`` times a log-normal, ``beta`` uniform or fixed."""
    rng = np.random.default_rng(seed)
    unit = lambda a: a / np.linalg.norm(a, axis=-1, keepdims=True)
    return {
        "q": unit(rng.normal(size=(t, H, DK))).astype(np.float32),
        "k": unit(rng.normal(size=(t, H, DK))).astype(np.float32),
        "v": rng.normal(size=(t, H, DV)).astype(np.float32),
        "g": (-decay * np.exp(rng.normal(size=(t, H, DK)) - 1)).astype(np.float32),
        "beta": (rng.uniform(size=(t, H)) if beta is None
                 else np.full((t, H), beta)).astype(np.float32),
    }


def _recurrence(a: dict, lo: int, hi: int) -> np.ndarray:
    """Tokens ``lo..hi-1`` as one document, token by token, float64."""
    o = np.zeros((hi - lo, H, DV))
    for h in range(H):
        s = np.zeros((DK, DV))
        for i in range(lo, hi):
            k, q = a["k"][i, h].astype(np.float64), a["q"][i, h].astype(np.float64)
            s = np.exp(a["g"][i, h].astype(np.float64))[:, None] * s
            s = s + float(a["beta"][i, h]) * np.outer(k, a["v"][i, h] - k @ s)
            o[i - lo, h] = s.T @ q
    return o


def _layout(lengths, t: int):
    seg, pos, off = np.full(t, len(lengths), np.int32), np.zeros(t, np.int32), 0
    for j, n in enumerate(lengths):
        seg[off: off + n], pos[off: off + n] = j, np.arange(n)
        off += n
    return seg, pos, seg < len(lengths)


def _scan(fn, a: dict, seg, pos, valid) -> np.ndarray:
    dev = lambda v: None if v is None else jnp.asarray(v)
    return np.asarray(jax.jit(fn)(*(jnp.asarray(a[k]) for k in ("q", "k", "v", "g", "beta")),
                                  dev(seg), dev(pos), dev(valid)))


def _pallas(*args):
    return K.kda_scan_pallas(*args, interpret=True)


FORMS = [pytest.param(K.kda_scan_xla, id="xla"), pytest.param(_pallas, id="pallas-interpret")]


def _check(form, a, lengths, t):
    seg, pos, valid = _layout(lengths, t)
    got = _scan(form, a, seg, pos, valid)
    assert np.isfinite(got).all()
    off = 0
    for n in lengths:
        want = _recurrence(a, off, off + n)
        assert np.abs(got[off: off + n] - want).max() <= TOL * np.abs(want).max(), (lengths, off)
        off += n


@pytest.mark.parametrize("form", FORMS)
def test_one_document_over_several_chunks(form):
    t = 3 * K.CHUNK + 21
    a = _inputs(t, seed=1)
    got = _scan(form, a, None, np.arange(t), None)
    want = _recurrence(a, 0, t)
    assert np.abs(got - want).max() <= TOL * np.abs(want).max()


@pytest.mark.parametrize("form", FORMS)
@pytest.mark.parametrize("lengths,t", [
    ((64, 30, 34, 100), 240),   # borders on a chunk edge, mid-chunk, on the next edge
    ((10, 5, 70, 1, 40), 160),  # three documents inside the first chunk, one token alone
    ((17, 16, 15), 64),         # borders inside and on sub-blocks of 16; no padding
])
def test_packed_documents_are_kept_apart_wherever_their_borders_fall(form, lengths, t):
    _check(form, _inputs(t, seed=len(lengths)), lengths, t)


@pytest.mark.parametrize("form", FORMS)
@pytest.mark.parametrize("beta", [1e-4, 1.0])
def test_beta_near_zero_and_at_one(form, beta):
    lengths, t = (90, 40), 140
    _check(form, _inputs(t, seed=3, decay=0.05, beta=beta), lengths, t)


@pytest.mark.parametrize("form", FORMS)
def test_strong_decays_where_the_factored_form_overflows(form):
    """Log-decays of 20 nats a token and more: the running sum inside a
    chunk reaches thousands, so ``exp(-G_j)`` is infinite in float32 and a
    form that factors the pair's decay would read NaN."""
    lengths, t = (100, 50), 160
    a = _inputs(t, seed=4, decay=60.0)
    assert np.median(a["g"]) < -20
    g = np.cumsum(a["g"][:64].astype(np.float32), axis=0)
    with np.errstate(over="ignore"):
        assert np.isinf(np.exp(-g)).any()  # what the factored form would multiply by
    _check(form, a, lengths, t)


def test_padding_neither_writes_nor_decays():
    """A padded tail (``valid`` false) leaves every real token's output as it
    is, whatever the padding's inputs hold."""
    lengths, t = (50, 30), 128
    a = _inputs(t, seed=5)
    seg, pos, valid = _layout(lengths, t)
    base = _scan(K.kda_scan_xla, a, seg, pos, valid)
    noisy = {k: v.copy() for k, v in a.items()}
    for k in noisy:
        noisy[k][80:] = 7.0 if k != "g" else -3.0
    np.testing.assert_array_equal(_scan(K.kda_scan_xla, noisy, seg, pos, valid)[:80], base[:80])


def test_the_kernel_and_the_xla_form_agree_with_four_heads_a_step():
    """The kernel's head groups (four heads a grid step, as on the chip)
    against the XLA form."""
    rng = np.random.default_rng(6)
    t, h = 150, 8
    unit = lambda x: x / np.linalg.norm(x, axis=-1, keepdims=True)
    a = {"q": unit(rng.normal(size=(t, h, DK))), "k": unit(rng.normal(size=(t, h, DK))),
         "v": rng.normal(size=(t, h, DV)), "g": -np.exp(rng.normal(size=(t, h, DK)) - 1),
         "beta": rng.uniform(size=(t, h))}
    a = {k: v.astype(np.float32) for k, v in a.items()}
    seg, pos, valid = _layout((70, 60), t)
    assert h % K.HEADS_PER_STEP == 0
    kernel = functools.partial(K.kda_scan_pallas, interpret=True)
    np.testing.assert_allclose(_scan(kernel, a, seg, pos, valid),
                               _scan(K.kda_scan_xla, a, seg, pos, valid), rtol=0, atol=1e-5)


@pytest.mark.parametrize("scale", [0.1, 1.0])
def test_the_inverse_by_products_where_keys_are_nearly_parallel(scale):
    """``(I + A)^-1`` of a strictly lower ``A`` [64, 64] of equal entries
    (equal keys at ``beta`` = ``scale``: the worst case of the delta rule)
    against numpy's inverse.  The product form over the whole chunk would
    pass through partial products of ~1e18 here."""
    c = K.CHUNK
    a = np.tril(np.full((c, c), scale, np.float32), -1)
    got = np.asarray(jax.jit(K._unit_lower_inverse)(jnp.asarray(a)))
    want = np.linalg.inv(np.eye(c) + a.astype(np.float64))
    assert np.abs(got - want).max() <= 1e-5 * max(1.0, np.abs(want).max())
