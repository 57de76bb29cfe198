"""A causal language model as a sentence embedder: window, full and latent
attention, gated short convolutions, gated delta rules, grouped queries,
rotary positions, routed and shared experts, the final-norm state of the last
token as the vector.

The forward of a decoder-only model with nothing generated (no output head,
no cache), the way language-model embedders are deployed (E5-Mistral,
arXiv:2401.00368).  :class:`SentenceEncoder` builds it from a
:class:`CausalMoeEmbedderConfig` as it builds the BERT encoder from an
``EncoderConfig``; tokenizing, bucketing, dispatch and the spans around them
are the ones every encoder takes.

Layer ``l`` of kind ``"full"`` or ``"window"`` (``x`` [T, D]; ``H_l`` query
heads, ``KV`` key/value heads of size ``hd``; no bias; RMS norms):

1. ``a = rmsnorm(x)``; ``q = a Wq`` [T, H_l, hd], ``k = a Wk``, ``v = a Wv``
   [T, KV, hd]; with ``qk_norm`` each head of ``q`` and of ``k`` is
   RMS-normed over its ``hd`` values (a scale of ``hd`` each).
2. rotary on the first ``rotary_factor * hd`` dimensions of ``q`` and ``k``
   (half-split pairing), plain or YaRN, by the layer's kind.
3. query head ``h`` reads KV head ``h // (H_l / KV)``; causal scores
   ``q_i . k_j / sqrt(hd)``, on window layers only ``i - j < window``;
   softmax in float32.
4. with ``attention_gate``: ``g = sigmoid(a Wg)`` [T, H_l],
   ``x += concat_h(g_h o_h) Wo``; without: ``x += concat_h(o_h) Wo``.
5. ``b = rmsnorm(x)``; dense layers: ``x += (silu(b Wg) * (b Wu)) Wd``; sparse
   layers: ``x += routed_experts(b) + shared_expert(b)``
   (:mod:`pathway_tpu.ops.routed_experts`; ``router_scoring`` picks the
   softmax router or the sigmoid one with its selection bias; no shared
   expert where ``shared_expert_dim`` is 0).
6. after the last layer ``rmsnorm``; a row's vector is the state of its last
   real token (the index normalises it).

A layer of kind ``"latent"`` (multi-head latent attention, arXiv:2405.04434,
as the DeepSeek-V3 lineage configures it) replaces steps 1-4
(:func:`_attend_latent`): queries and keys/values come through two low-rank
projections with an RMS norm inside each, a head's query and key are a
rotary-free part and a rotary part side by side, the rotary key is ONE head
shared by all, the value has a size of its own, there is no gate.  With
``latent_q_rank`` 0 the query is one direct projection ``q = a W_q`` (no
low-rank path, no norm); with ``latent_rotary`` None no dimension turns (the
rotary part is kept in the product as it is, the key's one head under all).
It runs
here in the prefill ("expanded") form: keys and values are expanded from the
latent for every token and a (query, key, head) triple costs ``qk_nope_dim +
qk_rope_dim + v_head_dim`` multiply-adds (192 + 128), where the absorbed form
(the up-projections folded into query and output, scores taken against the
latent itself) costs ``kv_lora_rank + qk_rope_dim + kv_lora_rank`` (576 +
512).  The absorbed form pays where a cache of latents is read back; an
embedder keeps no cache, so there is nothing it would save.

A layer of kind ``"conv"`` (the gated short convolution of LFM2,
:func:`_conv_mixer`) replaces steps 1-4 with ``[B | C | h] = a W_in`` (three
blocks of D, in that order), ``u = B * h``, ``v`` = the causal depthwise
convolution of ``u`` over ``conv_taps`` tokens with no bias
(:func:`pathway_tpu.ops.ssd_scan.causal_conv`: a tap that would reach into
the document before is dropped, so a document convolves on a packed axis as
it does alone), ``x += (C * v) W_out``.

A layer of kind ``"kda"`` (Kimi Delta Attention, arXiv:2510.26692;
:func:`_kda_mixer`) replaces steps 1-4 with a gated delta rule over
``kda_heads`` heads of ``kda_head_dim`` (``P`` = their product):
``q, k, v = silu(causal_conv(a W_qkv))`` over ``conv_taps`` tokens with no
bias; ``q`` and ``k`` L2-normed a head, ``q`` times ``1/sqrt(kda_head_dim)``;
a decay a channel ``g = -exp(A_log) softplus((a W_fa) W_fb + dt_bias)`` and
``beta = sigmoid(a W_b)``; ``o`` the delta rule's output
(:func:`pathway_tpu.ops.kda_scan.kda_scan`, the state zero at a document's
start); ``x += (rmsnorm_head(o) * sigmoid((a W_ga) W_gb)) W_o``.  The two
low-rank paths are of rank ``kda_head_dim``.

Precision: weights are held in ``param_dtype`` (bfloat16) and products take
``dtype`` (bfloat16) operands with float32 accumulation; the residual
stream, norms, rotary tables, softmax, the router, the combine and the
convolution's gates and taps, and the delta rule's norms, decays,
gates and scan are float32.

Attention is XLA over blocks of ``q_block`` queries.  A query block visits
the key blocks from the first that holds a token some query of it may see
(the start of the text its first token belongs to; on window layers no
further back than the window reaches) up to itself, and a block of pure
padding visits none: no [T, T] score matrix is ever held, a window layer's
work grows with T, not T^2, and a text never pays for the keys of the texts
packed before it.  The softmax is the one-shot one taken in two passes over
those blocks (the row's maximum and sum first, then ``exp(s - max) / sum``
rounded to the compute dtype and multiplied by the values), so what is
rounded where does not depend on how many blocks a row saw.

Two layouts over one parameter tree, as the BERT encoder has them: the
dense forward ([batch, seq] ids and mask, padding behind the text) and the
packed forward (rows concatenated along one token axis with segment ids
and positions).  The packed one serves (``attention_impl="ragged"``, the
default): the routed layers take a launch's tokens as one axis, so
documents that share a launch share one read of every expert they touch,
where a launch a document reads nearly all of them again; and rows of any
lengths go together, so the programs are one per TOKEN bucket
(``token_buckets``, four) and not one per (rows, sequence) pair.  A lone
document rides the same programs.  The dense forward stays for what feeds
one text's states to one block (:meth:`CausalMoeEmbedder.layer`) and for
``attention_impl="xla"``.  A padding token is routed to no expert, and
under a causal mask no real token sees one.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, ClassVar

import jax
import jax.numpy as jnp
import numpy as np

from ..ops.grouped_matmul import grouped_matmul_impl
from ..ops.kda_scan import kda_scan
from ..ops.routed_experts import launch_counters, routed_experts
from ..ops.ssd_scan import causal_conv

__all__ = ["RotarySpec", "CausalMoeEmbedderConfig", "CausalMoeEmbedder",
           "rotary_inv_freq", "init_params", "count_params"]

@dataclasses.dataclass(frozen=True)
class RotarySpec:
    """Rotary positions of one kind of layer.  ``yarn_factor`` > 1 selects
    YaRN (arXiv:2309.00071) as ``transformers`` computes it."""

    theta: float = 10_000.0
    rotary_factor: float = 1.0
    yarn_factor: float = 1.0
    original_max_len: int = 4096
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    #: multiplies cosine and sine; None: ``0.1 ln(yarn_factor) + 1``
    attention_factor: float | None = None
    #: pairs are ``(2i, 2i+1)`` (``rope_interleave``) and not ``(i, i +
    #: rot/2)``: the rotated part comes out as ``[evens | odds]``
    interleaved: bool = False


@dataclasses.dataclass(frozen=True)
class CausalMoeEmbedderConfig:
    """Laguna-XS.2's widths by default, at the five leading layers (the
    dense one and one period of three window layers and a full one).  A
    field that one kind of layer alone reads (``head_dim``, ``num_kv_heads``
    and ``window`` the grouped-query kinds; the ``latent_*`` group the latent
    kind) is checked only where a layer of that kind is present."""

    vocab_size: int = 100_352
    hidden_dim: int = 2048
    head_dim: int = 128
    num_kv_heads: int = 8
    #: per layer: "full", "window", "latent", "conv" or "kda"; its query heads
    #: (read by the attention kinds alone); "dense" or "sparse"
    layer_types: tuple[str, ...] = ("full", "window", "window", "window", "full")
    heads_per_layer: tuple[int, ...] = (48, 64, 64, 64, 48)
    mlp_types: tuple[str, ...] = ("dense", "sparse", "sparse", "sparse", "sparse")
    window: int = 512
    full_rotary: RotarySpec = RotarySpec(
        theta=500_000.0, rotary_factor=0.5, yarn_factor=64.0,
        original_max_len=4096, beta_fast=64.0, beta_slow=1.0,
        attention_factor=1.4158883083359672)
    window_rotary: RotarySpec = RotarySpec(theta=10_000.0)
    #: latent layers: the ranks of the query's (0: a direct projection) and
    #: the key/value's low-rank paths, a head's rotary-free and rotary parts
    #: (the rotary key is one head under all), the value's size, rotary over
    #: the whole rotary part (None: no rotary)
    latent_q_rank: int = 1536
    latent_kv_rank: int = 512
    latent_nope_dim: int = 128
    latent_rope_dim: int = 64
    latent_v_dim: int = 128
    latent_rotary: RotarySpec | None = RotarySpec(theta=32_000_000.0, interleaved=True)
    #: grouped-query layers: per-head RMS norms of q and k before rotary;
    #: the per-head sigmoid gate on the attention's output
    qk_norm: bool = False
    attention_gate: bool = True
    #: conv and kda layers: tokens the causal depthwise convolution reaches
    conv_taps: int = 3
    #: kda layers: heads of the delta rule and their size (also the rank of
    #: the decay's and the output gate's low-rank paths)
    kda_heads: int = 32
    kda_head_dim: int = 128
    dense_mlp_dim: int = 8192
    #: experts the router chooses among; this chip holds ``held_experts`` of
    #: them (None: all) from ``first_expert`` on (expert parallelism: the
    #: others lie on other chips, and their part of a layer is not computed here)
    num_experts: int = 256
    held_experts: int | None = None
    first_expert: int = 0
    top_k: int = 8
    expert_dim: int = 512
    #: 0: the sparse layers hold no shared expert
    shared_expert_dim: int = 512
    routed_scaling: float = 2.5
    #: "softmax", or "sigmoid": the sparse layers then hold a per-expert
    #: ``bias`` that enters the choice of experts and not their weights
    router_scoring: str = "softmax"
    #: the sigmoid router's weights are divided by their sum + this
    router_eps: float = 1e-20
    rms_eps: float = 1e-6
    #: longest row the dispatch takes; rotary positions need no table
    max_len: int = 2048
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.bfloat16
    seq_buckets: tuple[int, ...] = (32, 64, 128, 256, 512, 1024, 2048)
    #: rows of a DENSE launch (``attention_impl="xla"``, and the row count a
    #: fused serving tick hands the search): one, because each (rows,
    #: sequence) program of a model this size takes seconds to compile and
    #: about a second to load.  The packed dispatch does not read it
    batch_buckets: tuple[int, ...] = (1,)
    #: queries a block of attention takes at once
    q_block: int = 512
    #: "ragged": a call's rows go out packed along one token axis, as many
    #: to a launch as ``token_buckets[-1]`` tokens hold, so an expert is
    #: read once a launch and not once a document; "xla": the dense
    #: [batch, seq] dispatch, one row a launch
    attention_impl: str = "ragged"
    #: token counts a packed launch is padded to, each one compiled
    #: program; the last is the most a launch holds, and a call over it
    #: goes out as several launches.  Even steps and not a doubling
    #: ladder: on a TPU v5e a launch costs 17 ms for the experts plus
    #: 12.5 us a token of its BUCKET, padding or not, so the quarter of a
    #: launch that a doubled bucket pads on average costs what a second
    #: launch's read of the experts does.  Four and not more: each program
    #: takes 9-14 s to compile and 1.9 s to load, and six put set-up a
    #: tenth over what five dense programs took (PERF.md 6, PR 32)
    token_buckets: tuple[int, ...] = (1536, 3072, 4608, 6144)

    program_name: ClassVar[str] = "pw_moe_embedder_forward"
    emb_dim: ClassVar[None] = None  # the vector is the hidden state
    #: row counts the packed launch's ``starts`` operand is padded to: one,
    #: so that how many documents share a launch mints no program
    packed_row_buckets: ClassVar[tuple[int, ...]] = (32,)
    #: attention never unpacks to a dense [rows, sequence] shape: rows of
    #: any lengths share a packed launch, which carries no sequence bucket
    packed_unpacks_rows: ClassVar[bool] = False
    #: the first packed dispatch launches every token bucket once on
    #: padding: a program takes seconds to compile and a second to load,
    #: which a lone short document would otherwise pay under live traffic
    warm_packed: ClassVar[bool] = True

    def __post_init__(self):
        n = len(self.layer_types)
        if not (len(self.heads_per_layer) == len(self.mlp_types) == n):
            raise ValueError("layer_types, heads_per_layer and mlp_types "
                             "must name the same layers")
        if not set(self.layer_types) <= {"full", "window", "latent", "conv", "kda"}:
            raise ValueError(f"layer_types {self.layer_types}")
        if any(h % self.num_kv_heads for kind, h in zip(self.layer_types, self.heads_per_layer)
               if kind in ("full", "window")):
            raise ValueError("a grouped-query layer's query heads must be a multiple "
                             f"of num_kv_heads={self.num_kv_heads}")
        if "latent" in self.layer_types and (self.latent_rope_dim % 2 or self.latent_q_rank < 0
                                             or min(self.latent_kv_rank, self.latent_nope_dim,
                                                    self.latent_rope_dim, self.latent_v_dim) < 1):
            raise ValueError("a latent layer needs positive ranks and head parts, "
                             "the rotary part even")
        if self.router_scoring not in ("softmax", "sigmoid"):
            raise ValueError(f"router_scoring {self.router_scoring!r}")
        if {"conv", "kda"} & set(self.layer_types) and self.conv_taps < 1:
            raise ValueError(f"conv_taps {self.conv_taps}")
        if not 0 <= self.first_expert <= self.first_expert + self.experts_here <= self.num_experts:
            raise ValueError(f"experts {self.first_expert}..+{self.experts_here} of "
                             f"{self.num_experts}")

    @property
    def experts_here(self) -> int:
        """Routed experts this chip holds a layer."""
        return self.num_experts if self.held_experts is None else self.held_experts

    @property
    def num_layers(self) -> int:
        return len(self.layer_types)

    def build_models(self):
        return CausalMoeEmbedder(self), CausalMoeEmbedder(self, packed=True)


def rotary_inv_freq(spec: RotarySpec, head_dim: int) -> tuple[np.ndarray, float]:
    """Inverse frequencies [rotary_dim / 2] (float64) and the factor that
    multiplies cosine and sine, as ``transformers``
    ``_compute_default_rope_parameters`` / ``_compute_yarn_parameters``."""
    dim = int(head_dim * spec.rotary_factor)
    pos_freqs = spec.theta ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
    if spec.yarn_factor <= 1.0:
        return 1.0 / pos_freqs, 1.0
    extrapolation = 1.0 / pos_freqs
    interpolation = 1.0 / (spec.yarn_factor * pos_freqs)

    def correction_dim(rotations: float) -> float:
        return (dim * math.log(spec.original_max_len / (rotations * 2 * math.pi))
                / (2 * math.log(spec.theta)))

    low = max(math.floor(correction_dim(spec.beta_fast)), 0)
    high = min(math.ceil(correction_dim(spec.beta_slow)), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2, dtype=np.float64) - low) / (high - low), 0, 1)
    extrapolated = 1.0 - ramp
    inv_freq = interpolation * (1 - extrapolated) + extrapolation * extrapolated
    factor = spec.attention_factor
    if factor is None:
        factor = 0.1 * math.log(spec.yarn_factor) + 1.0
    return inv_freq, float(factor)


def _rms_norm(x, scale, eps: float):
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * scale.astype(jnp.float32)


def _rotate(x, pos, spec: RotarySpec):
    """``x`` [T, H, hd], ``pos`` [T] -> rotated, float32.  Interleaved
    pairs are first gathered to ``[evens | odds]``, as the published model
    does, and then rotated as halves; the result stays in that order, which
    leaves a score unchanged where query and key are permuted alike."""
    inv_freq, factor = rotary_inv_freq(spec, x.shape[-1])
    rot = 2 * inv_freq.shape[0]
    angles = pos.astype(jnp.float32)[:, None] * jnp.asarray(inv_freq, jnp.float32)[None, :]
    cos = (jnp.cos(angles) * factor)[:, None, :]
    sin = (jnp.sin(angles) * factor)[:, None, :]
    x = x.astype(jnp.float32)
    if spec.interleaved:
        x1, x2, rest = x[..., 0:rot:2], x[..., 1:rot:2], x[..., rot:]
    else:
        x1, x2, rest = x[..., : rot // 2], x[..., rot // 2: rot], x[..., rot:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin, rest], axis=-1)


def _attention(q, k, v, pos, seg, valid, *, window: int | None, q_block: int):
    """Causal grouped-query attention of one token axis.  ``q`` [T, H, hd],
    ``k`` [T, KV, hd], ``v`` [T, KV, vd] in the compute dtype: scores and
    their scale go by ``hd``, the result by the value's own ``vd``, and ``KV
    == H`` is a group of one.  ``pos`` [T] positions in the row; ``seg`` [T]
    the row of each token and ``valid`` [T] whether it is one (both None:
    one row, every token real).  Returns [T, H, vd] float32.  Query block
    ``i`` visits key blocks ``first..i``: ``first`` holds the start of the
    row its first token belongs to (a later row of the block starts later),
    on window layers no further back than the window reaches; a block whose
    first token is padding visits none."""
    t, h, hd = q.shape
    kv, vd = k.shape[1], v.shape[-1]
    bq = min(q_block, t)
    blocks = -(-t // bq)
    if blocks * bq > t:  # whole blocks: what is added lies behind every token
        behind = lambda a: a if a is None else jnp.pad(
            a, ((0, blocks * bq - t),) + ((0, 0),) * (a.ndim - 1))
        q, k, v, pos, seg, valid = (behind(a) for a in (q, k, v, pos, seg, valid))
    q = q.reshape(blocks, bq, kv, h // kv, hd)
    back = 0 if window is None else -(-(window - 1) // bq)  # key blocks behind
    token = jnp.arange(blocks * bq)

    def block(i):
        lo = i * bq
        at = lambda a, start=lo: jax.lax.dynamic_slice_in_dim(a, start, bq)
        q_i, token_i, pos_i = q[i], at(token), at(pos)
        first = 0 if seg is None else (lo - pos[lo]) // bq
        if window is not None:
            first = jnp.maximum(first, i - back)
        last = i + 1 if valid is None else jnp.where(valid[lo], i + 1, first)

        def scores(j):
            s = jnp.einsum("qkgd,tkd->kgqt", q_i, at(k, j * bq),
                           preferred_element_type=jnp.float32) / math.sqrt(hd)
            see = at(token, j * bq)[None, :] <= token_i[:, None]
            if seg is not None:
                see &= at(seg)[:, None] == at(seg, j * bq)[None, :]
            if window is not None:
                see &= pos_i[:, None] - at(pos, j * bq)[None, :] < window
            return jnp.where(see[None, None], s, -1e30)

        def max_and_sum(j, carry):
            m, l = carry
            s = scores(j)
            m_new = jnp.maximum(m, jnp.max(s, axis=-1))
            return m_new, (l * jnp.exp(m - m_new)
                           + jnp.sum(jnp.exp(s - m_new[..., None]), axis=-1))

        rows = (kv, h // kv, bq)
        m, l = jax.lax.fori_loop(
            first, last, max_and_sum,
            (jnp.full(rows, -1e30, jnp.float32), jnp.zeros(rows, jnp.float32)))

        def weigh(j, acc):
            p = jnp.exp(scores(j) - m[..., None]) / l[..., None]
            return acc + jnp.einsum("kgqt,tkd->qkgd", p.astype(v.dtype), at(v, j * bq),
                                    preferred_element_type=jnp.float32)

        return jax.lax.fori_loop(first, last, weigh,
                                 jnp.zeros((bq, kv, h // kv, vd), jnp.float32))

    return jax.lax.map(block, jnp.arange(blocks)).reshape(blocks * bq, h, vd)[:t]


def _gated_mlp(x, w_gate_up, w_down, gate_scale: float = 1.0):
    """``(silu(gate_scale * (x Wg)) * (x Wu)) Wd`` -> float32; gate columns
    first."""
    h = jnp.dot(x, w_gate_up, preferred_element_type=jnp.float32)
    f = h.shape[-1] // 2
    gate = h[..., :f] if gate_scale == 1.0 else h[..., :f] * gate_scale
    act = (jax.nn.silu(gate) * h[..., f:]).astype(x.dtype)
    return jnp.dot(act, w_down, preferred_element_type=jnp.float32)


def _attend_row(cfg: CausalMoeEmbedderConfig, i: int, p, a, pos, seg, valid):
    """Steps 1-4 of layer ``i`` for one token axis: ``a`` [T, D] the normed
    input (float32) -> the attention's (or the convolution's) addition to
    the residual, float32."""
    if cfg.layer_types[i] == "latent":
        return _attend_latent(cfg, p, a, pos, seg, valid)
    if cfg.layer_types[i] == "conv":
        return _conv_mixer(cfg, p, a, pos)
    if cfg.layer_types[i] == "kda":
        return _kda_mixer(cfg, p, a, pos, seg, valid)
    dt = cfg.dtype
    full = cfg.layer_types[i] == "full"
    spec = cfg.full_rotary if full else cfg.window_rotary
    ad = a.astype(dt)
    q = jnp.einsum("td,dhe->the", ad, p["wq"], preferred_element_type=jnp.float32)
    k = jnp.einsum("td,dhe->the", ad, p["wk"], preferred_element_type=jnp.float32)
    v = jnp.einsum("td,dhe->the", ad, p["wv"], preferred_element_type=jnp.float32)
    if cfg.qk_norm:
        q, k = _rms_norm(q, p["q_norm"], cfg.rms_eps), _rms_norm(k, p["k_norm"], cfg.rms_eps)
    q, k = _rotate(q, pos, spec), _rotate(k, pos, spec)
    o = _attention(q.astype(dt), k.astype(dt), v.astype(dt), pos, seg, valid,
                   window=None if full else cfg.window, q_block=cfg.q_block)
    if cfg.attention_gate:
        o = o * jax.nn.sigmoid(
            jnp.dot(ad, p["wg"], preferred_element_type=jnp.float32))[:, :, None]
    return jnp.einsum("the,hed->td", o.astype(dt), p["wo"], preferred_element_type=jnp.float32)


def _conv_mixer(cfg: CausalMoeEmbedderConfig, p, a, pos):
    """A conv layer's mixer for one token axis: ``[B | C | h] = a W_in``,
    ``(C * causal_conv(B * h)) W_out``; the gates and taps float32, the
    taps dropped across a document's start by ``pos``."""
    f32 = dict(preferred_element_type=jnp.float32)
    bch = jnp.dot(a.astype(cfg.dtype), p["w_in"], **f32)
    d = bch.shape[-1] // 3
    v = causal_conv(bch[:, :d] * bch[:, 2 * d:], p["conv"], None, pos)
    return jnp.dot((bch[:, d: 2 * d] * v).astype(cfg.dtype), p["w_out"], **f32)


def _l2_norm(x, eps: float = 1e-6):
    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + eps)


def _kda_mixer(cfg: CausalMoeEmbedderConfig, p, a, pos, seg, valid):
    """A kda layer's mixer for one token axis (see the module): the three
    projections in one product, their taps dropped across a document's start
    by ``pos``, the delta rule over the axis with ``seg`` and ``valid``."""
    f32 = dict(preferred_element_type=jnp.float32)
    dt, h, dk = cfg.dtype, cfg.kda_heads, cfg.kda_head_dim
    ad = a.astype(dt)
    low_rank = lambda wa, wb: jnp.dot(jnp.dot(ad, p[wa], **f32).astype(dt), p[wb], **f32)
    qkv = jax.nn.silu(causal_conv(jnp.dot(ad, p["w_qkv"], **f32), p["conv"], None, pos))
    q, k, v = (qkv[:, n * h * dk:(n + 1) * h * dk].reshape(-1, h, dk) for n in range(3))
    decay = (low_rank("w_fa", "w_fb").reshape(-1, h, dk)
             + p["dt_bias"].astype(jnp.float32).reshape(h, dk))
    g = -jnp.exp(p["A_log"].astype(jnp.float32))[:, None] * jax.nn.softplus(decay)
    beta = jax.nn.sigmoid(jnp.dot(ad, p["w_b"], **f32))
    o = kda_scan(_l2_norm(q) * dk ** -0.5, _l2_norm(k), v, g, beta, seg, pos, valid)
    o = _rms_norm(o, p["o_norm"], cfg.rms_eps) * jax.nn.sigmoid(
        low_rank("w_ga", "w_gb").reshape(-1, h, dk))
    return jnp.dot(o.reshape(-1, h * dk).astype(dt), p["w_o"], **f32)


def _attend_latent(cfg: CausalMoeEmbedderConfig, p, a, pos, seg, valid):
    """A latent layer's attention for one token axis, in the prefill form:
    ``c_q = rmsnorm(a W_dq)``, ``q = c_q W_uq`` [T, H, nope + rope]; ``[c_kv |
    k_pe] = a W_dkv`` with ``k_pe`` ONE head [T, rope], ``[k_nope | v] =
    rmsnorm(c_kv) W_ukv`` [T, H, nope + vd]; rotary on ``q``'s rotary part
    and on ``k_pe``; head ``h`` scores ``[q_nope | q_pe]_h . [k_nope_h |
    k_pe] / sqrt(nope + rope)`` under the causal mask and sums ``v_h``; no
    gate.  The two latent norms are float32 like every norm."""
    dt, nope = cfg.dtype, cfg.latent_nope_dim
    f32 = dict(preferred_element_type=jnp.float32)
    ad = a.astype(dt)
    if cfg.latent_q_rank:
        c_q = _rms_norm(jnp.dot(ad, p["wq_a"], **f32), p["q_norm"], cfg.rms_eps)
        q = jnp.einsum("tr,rhe->the", c_q.astype(dt), p["wq_b"], **f32)
    else:
        q = jnp.einsum("td,dhe->the", ad, p["wq"], **f32)
    ckv = jnp.dot(ad, p["wkv_a"], **f32)
    c_kv = _rms_norm(ckv[:, : cfg.latent_kv_rank], p["kv_norm"], cfg.rms_eps)
    kv = jnp.einsum("tr,rhe->the", c_kv.astype(dt), p["wkv_b"], **f32)
    if cfg.latent_rotary is None:
        q_pe, k_pe = q[..., nope:], ckv[:, None, cfg.latent_kv_rank:]
    else:
        q_pe = _rotate(q[..., nope:], pos, cfg.latent_rotary)
        k_pe = _rotate(ckv[:, None, cfg.latent_kv_rank:], pos, cfg.latent_rotary)
        q = jnp.concatenate([q[..., :nope], q_pe], axis=-1)
    k = jnp.concatenate(
        [kv[..., :nope], jnp.broadcast_to(k_pe, q_pe.shape)], axis=-1)  # the one rotary key
    o = _attention(q.astype(dt), k.astype(dt), kv[..., nope:].astype(dt), pos, seg, valid,
                   window=None, q_block=cfg.q_block)
    return jnp.einsum("the,hed->td", o.astype(dt), p["wo"], **f32)


def _layer(cfg: CausalMoeEmbedderConfig, i: int, p, x, pos, seg, valid):
    """One block: ``x`` [B, T, D] float32 (attention is a row's own; the
    routed layer takes all B*T tokens as one axis, so an expert's weights
    are read once for the launch).  ``seg`` None: each of the B rows is one
    text; else B is 1 and the row is texts packed end to end."""
    a = _rms_norm(x, p["attn_norm"], cfg.rms_eps)
    attend = functools.partial(_attend_row, cfg, i, p)
    if seg is None:
        x = x + jax.vmap(lambda a_, pos_: attend(a_, pos_, None, None))(a, pos)
    else:
        x = x + attend(a[0], pos[0], seg[0], valid[0])[None]
    b = _rms_norm(x, p["mlp_norm"], cfg.rms_eps)
    bd = b.astype(cfg.dtype)
    if cfg.mlp_types[i] == "dense":
        return x + _gated_mlp(bd, p["mlp"]["w_gate_up"], p["mlp"]["w_down"]), None
    m = p["moe"]
    flat = (x.shape[0] * x.shape[1],)
    # the router reads the float32 norm: a score rounded to bfloat16 on the
    # way in flips an expert as one rounded on the way out does
    routed, group_sizes = routed_experts(
        bd.reshape(flat + bd.shape[2:]), valid.reshape(flat), m["router"],
        m["w_gate_up"], m["w_down"], top_k=cfg.top_k, scaling=cfg.routed_scaling,
        router_input=b.reshape(flat + b.shape[2:]), scoring=cfg.router_scoring,
        bias=m.get("bias"), eps=cfg.router_eps, first_expert=cfg.first_expert)
    x = x + routed.reshape(x.shape)
    if "shared" in m:
        x = x + _gated_mlp(bd, m["shared"]["w_gate_up"], m["shared"]["w_down"])
    return x, group_sizes


def _tokens_forward(cfg, params, ids, pos, seg, valid):
    """Blocks and final norm: ``ids``, ``pos``, ``valid`` [B, T], ``seg``
    [B, T] or None (a row is one text) -> ([B, T, D] float32, the routed
    layers' group sizes)."""
    x = params["tok_emb"][ids].astype(jnp.float32)
    sizes = []
    for i in range(cfg.num_layers):
        x, group_sizes = _layer(cfg, i, params[f"layer_{i}"], x, pos, seg, valid)
        if group_sizes is not None:
            sizes.append(group_sizes)
    return _rms_norm(x, params["final_norm"], cfg.rms_eps), sizes


def _counters(cfg, sizes: list, lengths, valid):
    """int32 of one launch: the routed experts' four (``launch_counters``),
    and behind them, from a model with latent layers, its documents, real
    tokens, the tokens of its bucket and the (query, key) pairs the causal
    mask lets through (``L (L + 1) / 2`` a document of ``L`` tokens; once a
    launch, not once a layer); from a model with conv layers the first
    three of those.  ``flight_recorder.record_moe_launch`` adds them up."""
    moe = launch_counters(sizes) if sizes else jnp.zeros((4,), jnp.int32)
    held = [jnp.sum(lengths > 0), jnp.sum(lengths), jnp.int32(valid.size)]
    if "latent" in cfg.layer_types:
        held.append(jnp.sum(lengths * (lengths + 1) // 2))
    elif "conv" not in cfg.layer_types:
        return moe
    return jnp.concatenate([moe, jnp.stack(held).astype(jnp.int32)])


class CausalMoeEmbedder:
    """The model as :class:`SentenceEncoder` takes one: ``init`` and
    ``apply`` over ``{"params": tree}``.  ``apply`` returns (vectors
    float32, the launch's counters: :func:`_counters`); ``record_launch``
    is where the encoder sends the second, with the grouped product's
    implementation that ``apply`` was traced with."""

    def __init__(self, cfg: CausalMoeEmbedderConfig, packed: bool = False):
        self.cfg = cfg
        self.packed = packed
        #: ``grouped_matmul_impl()`` when ``apply`` was last traced (None:
        #: not yet, or no routed layer)
        self.grouped_impl: str | None = None

    def record_launch(self, counters) -> None:
        from ..internals.flight_recorder import record_moe_launch

        record_moe_launch(counters, grouped_impl=self.grouped_impl)

    def init(self, key, *_example):
        return {"params": init_params(self.cfg, key)}

    def layer(self, layer_params, i: int, x):
        """Block ``i`` alone over one text's states ``x`` [T, D] ->
        [T, D] float32, every token real: what a check calls to feed the
        program a reference's own input to that block."""
        t = x.shape[0]
        out, _sizes = _layer(self.cfg, i, layer_params, jnp.asarray(x, jnp.float32)[None],
                             jnp.arange(t)[None], None, jnp.ones((1, t), bool))
        return out[0]

    def apply(self, variables, *args, **kwargs):
        params = variables["params"]
        if "sparse" in self.cfg.mlp_types:
            self.grouped_impl = grouped_matmul_impl()
        if self.packed:
            return self._apply_packed(params, *args, **kwargs)
        return self._apply_dense(params, *args)

    def _apply_dense(self, params, ids, mask):
        """[B, S] ids and mask (padding behind the text) -> [B, D].  The
        mask alone says what is a token (an id says nothing: id 0 is a
        real token of many vocabularies).  The dispatcher marks the first
        position of a padding ROW as real, so that one token a padding row
        is routed like any other, and counted by the launch's counters."""
        cfg = self.cfg
        ids = ids.astype(jnp.int32)
        mask = mask.astype(jnp.int32)
        valid = mask > 0
        b, s = ids.shape
        pos = jnp.broadcast_to(jnp.arange(s), (b, s))
        x, sizes = _tokens_forward(cfg, params, ids, pos, None, valid)
        lengths = jnp.sum(mask, axis=1)
        last = jnp.maximum(lengths - 1, 0)
        return x[jnp.arange(b), last], _counters(cfg, sizes, lengths, valid)

    def _apply_packed(self, params, ids, pos, seg, starts, bounds=None, *,
                      dense_s: int | None = None):
        """Rows concatenated along one token axis (``ragged_prepare``):
        ``seg`` names each token's row, the pad tail carries
        ``seg == rows``; ``starts`` [rows] is where each row begins (rows
        past the launch's own begin at 0 and hold no token).  ``bounds``
        and ``dense_s`` serve the BERT encoder's kernel: here a query
        block's key range comes from ``pos`` and ``seg`` on the device."""
        cfg = self.cfg
        ids, pos, seg = (jnp.asarray(a, jnp.int32) for a in (ids, pos, seg))
        rows = starts.shape[0]
        valid = seg < rows
        x, sizes = _tokens_forward(
            cfg, params, ids[None], pos[None], seg[None], valid[None])
        lengths = jnp.zeros((rows + 1,), jnp.int32).at[seg].add(1)[:rows]
        last = starts.astype(jnp.int32) + jnp.maximum(lengths - 1, 0)
        return x[0][last], _counters(cfg, sizes, lengths, valid)


def init_params(cfg: CausalMoeEmbedderConfig, key):
    """A parameter tree drawn layer by layer (a tensor of experts is a
    gigabyte): token embeddings at unit scale, matrices at
    1/sqrt(fan-in), norms at one, a sigmoid router's selection bias at
    zero; a kda layer's ``A_log`` and ``dt_bias`` at zero, float32 as the
    scan reads them."""
    pd, d, hd, kv = cfg.param_dtype, cfg.hidden_dim, cfg.head_dim, cfg.num_kv_heads

    def normal(key, shape, fan_in):
        return (jax.random.normal(key, shape, jnp.float32) * fan_in ** -0.5).astype(pd)

    def mlp(key, shape_in, width):
        k1, k2 = jax.random.split(key)
        return {"w_gate_up": normal(k1, shape_in + (d, 2 * width), d),
                "w_down": normal(k2, shape_in + (width, d), width)}

    keys = jax.random.split(key, cfg.num_layers + 1)
    params = {"tok_emb": jax.random.normal(keys[0], (cfg.vocab_size, d), jnp.float32).astype(pd),
              "final_norm": jnp.ones((d,), pd)}
    for i in range(cfg.num_layers):
        h = cfg.heads_per_layer[i]
        k = jax.random.split(keys[i + 1], 8)
        layer = {"attn_norm": jnp.ones((d,), pd), "mlp_norm": jnp.ones((d,), pd)}
        if cfg.layer_types[i] == "conv":
            layer.update({
                "w_in": normal(k[0], (d, 3 * d), d),
                "conv": normal(k[1], (cfg.conv_taps, d), cfg.conv_taps),
                "w_out": normal(k[2], (d, d), d),
            })
        elif cfg.layer_types[i] == "kda":
            kh, dk = cfg.kda_heads, cfg.kda_head_dim
            kp = jax.random.split(k[0], 7)
            layer.update({
                "w_qkv": normal(kp[0], (d, 3 * kh * dk), d),
                "conv": normal(kp[1], (cfg.conv_taps, 3 * kh * dk), cfg.conv_taps),
                "w_fa": normal(kp[2], (d, dk), d), "w_fb": normal(kp[3], (dk, kh * dk), dk),
                "dt_bias": jnp.zeros((kh * dk,), jnp.float32),
                "A_log": jnp.zeros((kh,), jnp.float32),
                "w_b": normal(kp[4], (d, kh), d),
                "w_ga": normal(kp[5], (d, dk), d), "w_gb": normal(kp[6], (dk, kh * dk), dk),
                "o_norm": jnp.ones((dk,), pd), "w_o": normal(k[1], (kh * dk, d), kh * dk),
            })
        elif cfg.layer_types[i] == "latent":
            qr, kvr, nope = cfg.latent_q_rank, cfg.latent_kv_rank, cfg.latent_nope_dim
            rope, vd = cfg.latent_rope_dim, cfg.latent_v_dim
            layer.update({"wq": normal(k[0], (d, h, nope + rope), d)} if not qr else {
                "wq_a": normal(k[0], (d, qr), d), "q_norm": jnp.ones((qr,), pd),
                "wq_b": normal(k[1], (qr, h, nope + rope), qr)})
            layer.update({
                "wkv_a": normal(k[2], (d, kvr + rope), d), "kv_norm": jnp.ones((kvr,), pd),
                "wkv_b": normal(k[3], (kvr, h, nope + vd), kvr),
                "wo": normal(k[4], (h, vd, d), h * vd),
            })
        else:
            layer.update({
                "wq": normal(k[0], (d, h, hd), d), "wk": normal(k[1], (d, kv, hd), d),
                "wv": normal(k[2], (d, kv, hd), d), "wo": normal(k[4], (h, hd, d), h * hd),
            })
            if cfg.attention_gate:
                layer["wg"] = normal(k[3], (d, h), d)
            if cfg.qk_norm:
                layer["q_norm"], layer["k_norm"] = jnp.ones((hd,), pd), jnp.ones((hd,), pd)
        if cfg.mlp_types[i] == "dense":
            layer["mlp"] = mlp(k[5], (), cfg.dense_mlp_dim)
        else:
            layer["moe"] = {
                "router": normal(k[5], (d, cfg.num_experts), d),
                **mlp(k[6], (cfg.experts_here,), cfg.expert_dim),
            }
            if cfg.shared_expert_dim:
                layer["moe"]["shared"] = mlp(k[7], (), cfg.shared_expert_dim)
            if cfg.router_scoring == "sigmoid":  # float32, as the router reads it
                layer["moe"]["bias"] = jnp.zeros((cfg.num_experts,), jnp.float32)
        params[f"layer_{i}"] = layer
    return params


def count_params(params) -> int:
    return sum(int(np.prod(p.shape)) for p in jax.tree_util.tree_leaves(params))
