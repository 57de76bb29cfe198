"""The chunked state-space scan (``pathway_tpu/ops/ssd_scan.py``) against the
recurrence it stands for, computed token by token in float64, one document at
a time: over one document, and over a packed axis of documents whose lengths
straddle chunk ends, with a padded tail.  The convolution's taps at document
starts, with a bias and without one.  The Pallas kernel's body in interpret
mode against the XLA form.

Tolerance: both sides sum the same products in another order, in float32 on
one side: 2e-6 of the largest output (read here: 1e-7 to 4e-7).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from pathway_tpu.ops import ssd_scan as S

H, P, G, N = 4, 8, 2, 16
TOL = 2e-6


def _inputs(t: int, seed: int = 0):
    rng = np.random.default_rng(seed)
    return {
        "x": rng.normal(size=(t, H, P)).astype(np.float32),
        "dt": np.log1p(np.exp(rng.normal(size=(t, H)) - 1)).astype(np.float32),
        "A": -np.exp(rng.uniform(0, 2.5, H)).astype(np.float32),
        "B": rng.normal(size=(t, G, N)).astype(np.float32),
        "C": rng.normal(size=(t, G, N)).astype(np.float32),
        "D": rng.normal(size=H).astype(np.float32),
    }


def _recurrence(a: dict, lo: int, hi: int) -> np.ndarray:
    """Tokens ``lo..hi-1`` as one document, token by token, float64."""
    y = np.zeros((hi - lo, H, P))
    for h in range(H):
        state, g = np.zeros((P, N)), h // (H // G)
        for i in range(lo, hi):
            state = (np.exp(float(a["dt"][i, h]) * float(a["A"][h])) * state
                     + float(a["dt"][i, h]) * np.outer(a["x"][i, h], a["B"][i, g]))
            y[i - lo, h] = state @ a["C"][i, g] + a["D"][h] * a["x"][i, h]
    return y


def _layout(lengths, t: int):
    seg, pos, off = np.full(t, len(lengths), np.int32), np.zeros(t, np.int32), 0
    for j, n in enumerate(lengths):
        seg[off: off + n], pos[off: off + n] = j, np.arange(n)
        off += n
    return seg, pos, seg < len(lengths)


def _scan(fn, a: dict, seg, pos, valid, chunk: int) -> np.ndarray:
    dev = lambda v: None if v is None else jnp.asarray(v)
    return np.asarray(jax.jit(functools.partial(fn, chunk=chunk))(
        *(jnp.asarray(a[k]) for k in ("x", "dt", "A", "B", "C", "D")),
        dev(seg), dev(pos), dev(valid)))


def _pallas(*args, chunk):
    return S.ssd_scan_pallas(*args, chunk=chunk, interpret=True)


FORMS = [pytest.param(S.ssd_scan_xla, id="xla"), pytest.param(_pallas, id="pallas-interpret")]


@pytest.mark.parametrize("form", FORMS)
@pytest.mark.parametrize("t,chunk", [(5, 8), (24, 8), (30, 7)])
def test_one_document_equals_the_token_by_token_recurrence(form, t, chunk):
    a = _inputs(t, seed=t)
    got = _scan(form, a, None, np.arange(t), None, chunk)
    want = _recurrence(a, 0, t)
    assert np.abs(got - want).max() <= TOL * np.abs(want).max()


@pytest.mark.parametrize("form", FORMS)
@pytest.mark.parametrize("lengths,t,chunk", [
    ((5, 19, 8, 3, 16, 1), 64, 8),   # borders inside chunks, at their ends, a one-token document
    ((8, 8, 16), 40, 8),             # every border on a chunk's end
    ((3, 3, 3, 3, 3, 3, 3), 32, 8),  # several whole documents inside one chunk
    ((30, 2, 9), 48, 7),             # a chunk that divides neither the axis nor a document
])
def test_a_packed_axis_gives_each_document_what_it_gets_alone(form, lengths, t, chunk):
    a = _inputs(t, seed=len(lengths))
    seg, pos, valid = _layout(lengths, t)
    got = _scan(form, a, seg, pos, valid, chunk)
    assert np.isfinite(got).all()  # the padded tail too
    off = 0
    for n in lengths:
        want = _recurrence(a, off, off + n)
        assert np.abs(got[off: off + n] - want).max() <= TOL * np.abs(want).max()
        off += n


def test_a_packed_document_equals_the_same_form_over_the_document_alone():
    """Packed equals each document alone to float32 sums (both are held to
    the float64 recurrence above; here they are held to each other)."""
    lengths, t, chunk = (5, 19, 8), 40, 8
    a = _inputs(t, seed=11)
    seg, pos, valid = _layout(lengths, t)
    got = _scan(S.ssd_scan_xla, a, seg, pos, valid, chunk)
    off = 0
    for n in lengths:
        alone = _scan(S.ssd_scan_xla,
                      {k: (v[off: off + n] if v.shape[0] == t else v) for k, v in a.items()},
                      None, np.arange(n), None, chunk)
        assert np.abs(got[off: off + n] - alone).max() <= TOL * np.abs(alone).max()
        off += n


def test_padding_adds_nothing_and_carries_nothing():
    """What lies in the padded tail (and what a document before holds) does
    not reach a document: the same documents beside other neighbours."""
    lengths, t, chunk = (6, 11), 32, 8
    a = _inputs(t)
    seg, pos, valid = _layout(lengths, t)
    base = _scan(S.ssd_scan_xla, a, seg, pos, valid, chunk)
    other = {k: v.copy() for k, v in a.items()}
    for k in ("x", "dt", "B", "C"):
        other[k][17:] = 1e3 * (1 + np.abs(other[k][17:]))  # the tail, made loud
        other[k][:6] = np.abs(other[k][:6][::-1])  # and the first document changed
    again = _scan(S.ssd_scan_xla, other, seg, pos, valid, chunk)
    # not bit for bit: the running sum of a chunk passes through the document before
    assert np.abs(again[6:17] - base[6:17]).max() <= TOL * np.abs(base[6:17]).max()
    assert np.abs(again[:6] - base[:6]).max() > 0.1


def test_the_kernel_body_in_interpret_mode_equals_the_xla_form():
    lengths, t, chunk = (5, 19, 8, 3, 16, 1), 64, 8
    a = _inputs(t, seed=7)
    seg, pos, valid = _layout(lengths, t)
    xla, kernel = (_scan(f, a, seg, pos, valid, chunk) for f in (S.ssd_scan_xla, _pallas))
    assert np.abs(xla - kernel)[valid].max() <= 1e-6 * np.abs(xla[valid]).max()


def test_a_tap_of_the_convolution_is_dropped_at_a_documents_start():
    lengths, t, taps, channels = (5, 1, 2, 9, 4), 24, 4, 6
    rng = np.random.default_rng(3)
    x = rng.normal(size=(t, channels)).astype(np.float32)
    w = rng.normal(size=(taps, channels)).astype(np.float32)
    bias = rng.normal(size=channels).astype(np.float32)
    _seg, pos, _valid = _layout(lengths, t)
    got = np.asarray(S.causal_conv(jnp.asarray(x), jnp.asarray(w), jnp.asarray(bias),
                                   jnp.asarray(pos)))
    off = 0
    for n in lengths:  # conv1d over the document alone, padded by taps - 1 zeros on the left
        padded = np.concatenate([np.zeros((taps - 1, channels)), x[off: off + n]])
        want = np.stack([bias + sum(w[k] * padded[i + k] for k in range(taps)) for i in range(n)])
        assert np.abs(got[off: off + n] - want).max() < 1e-5
        off += n
    # a document's first token sees its own tap and the bias alone
    assert np.allclose(got[5], bias + w[taps - 1] * x[5], atol=1e-6)


def _causal_conv_with_bias(x, weight, bias, pos):
    """``causal_conv`` as it stood when a bias was required (Falcon-H1's
    call): the bias broadcast first, then the taps from the nearest back."""
    x = x.astype(jnp.float32)
    taps = weight.shape[0]
    out = jnp.broadcast_to(bias.astype(jnp.float32), x.shape)
    for back in range(taps):
        shifted = x if back == 0 else jnp.pad(x, ((back, 0), (0, 0)))[: x.shape[0]]
        shifted = jnp.where((pos >= back)[:, None], shifted, 0.0)
        out = out + shifted * weight[taps - 1 - back].astype(jnp.float32)
    return out


@pytest.mark.parametrize("jit", [False, True])
def test_the_convolution_with_a_bias_is_bit_for_bit_what_it_was(jit):
    """Falcon-H1's call (bfloat16 weights and bias, 4 taps, a packed axis)
    gives the same bits as before ``bias=None`` was allowed."""
    lengths, t, taps, channels = (5, 1, 2, 9, 4), 24, 4, 40
    rng = np.random.default_rng(11)
    x = jnp.asarray(rng.normal(size=(t, channels)), jnp.bfloat16)
    w = jnp.asarray(rng.normal(size=(taps, channels)), jnp.bfloat16)
    bias = jnp.asarray(rng.normal(size=channels), jnp.bfloat16)
    _seg, pos, _valid = _layout(lengths, t)
    pos = jnp.asarray(pos)
    now, before = S.causal_conv, _causal_conv_with_bias
    if jit:
        now, before = jax.jit(now), jax.jit(before)
    np.testing.assert_array_equal(np.asarray(now(x, w, bias, pos)),
                                  np.asarray(before(x, w, bias, pos)))


def test_no_bias_is_the_taps_alone():
    """``bias=None`` (LFM2's conv has none) is the convolution with a bias
    of zero, and a document's first token is its own tap alone."""
    lengths, t, taps, channels = (3, 6, 1, 7), 20, 3, 5
    rng = np.random.default_rng(12)
    x = jnp.asarray(rng.normal(size=(t, channels)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(taps, channels)), jnp.float32)
    _seg, pos, _valid = _layout(lengths, t)
    pos = jnp.asarray(pos)
    got = np.asarray(S.causal_conv(x, w, None, pos))
    np.testing.assert_array_equal(got, np.asarray(S.causal_conv(x, w, jnp.zeros(channels), pos)))
    np.testing.assert_array_equal(got[3], np.asarray(x[3] * w[taps - 1]))
