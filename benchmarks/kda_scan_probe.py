"""The delta rule's scan alone on one document of 2,048 tokens at Kimi-Linear's
published widths (layer 0's q, k, v, g, beta from the cell's seeded weights):
the Pallas kernel ``pw_kda_scan`` and the token-by-token float32 recurrence
of the plain reference, each against a float64 recurrence on the host, so
that ``layer_gap``'s reading in the KDA layers can be put down to one side.
The XLA twin of the scan runs beside them on the same device.  Prints the
median and largest error over tokens relative to |o|, and the median by
quarter of the document; then what the scan's precision rests on, each
against float64: the decay's running sum inside a chunk (``jnp.cumsum``),
``exp`` over [-30, 0] inside a Pallas kernel and in XLA, and a float32
product at ``Precision.HIGHEST`` inside a Pallas kernel and in XLA.

    python benchmarks/kda_scan_probe.py

Run from the root of a checkout on a TPU; elsewhere the kernel's place is
taken by the XLA form, which says nothing of the chip."""
import json
import os
import sys

import numpy as np

root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(root, "perfbench"))
sys.path.insert(0, root)
import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from checks import kimi_linear as reference  # noqa: E402
from encoders import kimi_linear as builder  # noqa: E402
from generators import file_drop_docs  # noqa: E402
from pathway_tpu.models import causal_moe_embedder as cme  # noqa: E402
from pathway_tpu.ops import kda_scan as K  # noqa: E402

config = json.load(open(os.path.join(root, "perfbench", "configs",
                               "vs-kimi-linear-48b-a3b-bf16-marcodoc.json")))
seed = 2148001601
cfg = builder.model_config(config)
p = builder.layer_params(config, seed, 0)
emb = builder.embedding_params(config, seed)
text = file_drop_docs.document(5, seed, config["document_words"])
ids = reference.tokenize(text, int(config["vocab_size"]), int(config["max_seq_length"]))
t = len(ids)
x = emb["tok_emb"][jnp.asarray(ids)].astype(jnp.float32)
a = cme._rms_norm(x, p["attn_norm"], cfg.rms_eps)


@jax.jit
def operands(p, a):
    f32 = dict(preferred_element_type=jnp.float32)
    dt, h, dk = cfg.dtype, cfg.kda_heads, cfg.kda_head_dim
    ad = a.astype(dt)
    low = lambda wa, wb: jnp.dot(jnp.dot(ad, p[wa], **f32).astype(dt), p[wb], **f32)
    pos = jnp.arange(a.shape[0])
    qkv = jax.nn.silu(cme.causal_conv(jnp.dot(ad, p["w_qkv"], **f32), p["conv"], None, pos))
    q, k, v = (qkv[:, n * h * dk:(n + 1) * h * dk].reshape(-1, h, dk) for n in range(3))
    g = -jnp.exp(p["A_log"])[:, None] * jax.nn.softplus(
        low("w_fa", "w_fb").reshape(-1, h, dk) + p["dt_bias"].reshape(h, dk))
    beta = jax.nn.sigmoid(jnp.dot(ad, p["w_b"], **f32))
    return cme._l2_norm(q) * dk ** -0.5, cme._l2_norm(k), v, g, beta


q, k, v, g, beta = operands(p, a)
pos = jnp.arange(t)
kernel = np.asarray(jax.jit(K.kda_scan)(q, k, v, g, beta, None, pos, None))


@jax.jit
def token_by_token(q, k, v, g, beta):
    def step(s, tok):
        q_t, k_t, v_t, g_t, b_t = tok
        s = jnp.exp(g_t)[:, :, None] * s
        k_s = jnp.einsum("hc,hcv->hv", k_t, s, precision=jax.lax.Precision.HIGHEST)
        s = s + b_t[:, None, None] * k_t[:, :, None] * (v_t - k_s)[:, None, :]
        return s, jnp.einsum("hcv,hc->hv", s, q_t, precision=jax.lax.Precision.HIGHEST)
    return jax.lax.scan(step, jnp.zeros((q.shape[1], q.shape[2], v.shape[2]), jnp.float32),
                        (q, k, v, g, beta))[1]


tbt = np.asarray(token_by_token(q, k, v, g, beta))
twin = np.asarray(jax.jit(K.kda_scan_xla)(q, k, v, g, beta, None, pos, None))
qn, kn, vn, gn, bn = (np.asarray(z, np.float64) for z in (q, k, v, g, beta))
s = np.zeros((qn.shape[1], qn.shape[2], vn.shape[2]))
exact = np.zeros_like(tbt, dtype=np.float64)
for i in range(t):
    s = np.exp(gn[i])[:, :, None] * s
    ks = np.einsum("hc,hcv->hv", kn[i], s)
    s = s + bn[i][:, None, None] * kn[i][:, :, None] * (vn[i] - ks)[:, None, :]
    exact[i] = np.einsum("hcv,hc->hv", s, qn[i])
scale = np.linalg.norm(exact.reshape(t, -1), axis=1)
for name, got in (("pw_kda_scan", kernel), ("kda_scan_xla", twin),
                  ("token-by-token float32", tbt)):
    err = np.linalg.norm((got - exact).reshape(t, -1), axis=1) / scale
    quarters = [float(np.median(err[j * t // 4:(j + 1) * t // 4])) for j in range(4)]
    print(f"kda-probe {name} against float64 over {t} tokens: median {np.median(err):.3g} "
          f"max {err.max():.3g}; median by quarter {[float(f'{e:.3g}') for e in quarters]}",
          flush=True)
print(f"kda-probe decay: median g {float(np.median(gn)):.3g}, min {float(gn.min()):.3g}", flush=True)
chunks = np.asarray(g).reshape(-1, K.CHUNK, *np.asarray(g).shape[1:])
running = np.asarray(jax.jit(lambda a: jnp.cumsum(a, axis=1))(jnp.asarray(chunks)), np.float64)
exact_running = np.cumsum(chunks.astype(np.float64), axis=1)
print(f"kda-probe the decay's running sum in a chunk (jnp.cumsum) against float64: largest "
      f"|error| {np.abs(running - exact_running).max():.3g} over |G| up to "
      f"{np.abs(exact_running).max():.3g}", flush=True)


from jax.experimental import pallas as pl  # noqa: E402

z = jnp.linspace(-30.0, 0.0, 64 * 128, dtype=jnp.float32).reshape(64, 128)
want = np.exp(np.asarray(z, np.float64))


def _exp_kernel(z_ref, o_ref):
    o_ref[...] = jnp.exp(z_ref[...])


for name, got in (("pallas", pl.pallas_call(_exp_kernel, out_shape=jax.ShapeDtypeStruct(
        z.shape, jnp.float32))(z)), ("xla", jax.jit(jnp.exp)(z))):
    rel = np.abs(np.asarray(got, np.float64) - want) / want
    print(f"kda-probe exp in {name} against float64 over [-30, 0]: median {np.median(rel):.3g} "
          f"max {rel.max():.3g}", flush=True)
ka, kb = jax.random.split(jax.random.PRNGKey(0))
x = jax.random.normal(ka, (64, 128), jnp.float32)
y = jax.random.normal(kb, (128, 128), jnp.float32)
exact_xy = np.asarray(x, np.float64) @ np.asarray(y, np.float64)


def _dot_kernel(x_ref, y_ref, o_ref):
    o_ref[...] = jnp.dot(x_ref[...], y_ref[...], precision=jax.lax.Precision.HIGHEST,
                         preferred_element_type=jnp.float32)


for name, got in (("pallas", pl.pallas_call(_dot_kernel, out_shape=jax.ShapeDtypeStruct(
        (64, 128), jnp.float32))(x, y)), ("xla", jax.jit(lambda a, b: jnp.dot(
            a, b, precision=jax.lax.Precision.HIGHEST))(x, y))):
    err = np.linalg.norm(np.asarray(got, np.float64) - exact_xy) / np.linalg.norm(exact_xy)
    print(f"kda-probe float32 product at HIGHEST in {name} against float64: {err:.3g}",
          flush=True)
