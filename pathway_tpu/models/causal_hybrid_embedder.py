"""A causal language model whose block runs attention and a Mamba-2 mixer in
parallel, as a sentence embedder: the final-norm state of the last token is
the vector.

The forward of a decoder-only model with nothing generated (no output head,
no cache), the way language-model embedders are deployed (E5-Mistral,
arXiv:2401.00368).  :class:`SentenceEncoder` builds it from a
:class:`CausalHybridEmbedderConfig` as it builds the BERT encoder from an
``EncoderConfig`` and the routed-expert embedder from its config;
tokenizing, bucketing, dispatch and the spans around them are the ones every
encoder takes.

The layer, at Falcon-H1-34B's widths (``x`` [T, 5120] float32 residual; RMS
norms, eps 1e-5; no bias but the convolution's):

1. ``x0 = tok_emb[ids] * embedding_multiplier``.
2. ``a = rmsnorm(x)``.  **Attention branch**: ``u = a *
   attention_in_multiplier``; ``q = u Wq`` [T, 20, 128], ``k = (u Wk) *
   key_multiplier`` [T, 4, 128], ``v = u Wv``; rotary over the whole head
   (half-split pairing), causal grouped-query softmax(``q k / sqrt(128)``)
   ``v``; ``attn = (o Wo) * attention_out_multiplier``.
3. **State-space branch, in parallel on the same** ``a``: ``u = a *
   ssm_in_multiplier``; ``[z | xBC | dt] = (u W_in) * mup_vector``, widths
   4,096 | 4,096 + 2 x 2 x 256 | 32, where ``mup_vector`` holds
   ``ssm_multipliers[0..4]`` over the zones z, x, B, C, dt; ``xBC =
   silu(conv(xBC))``, a causal depthwise convolution of 4 taps with bias;
   split ``x`` [T, 32, 128], ``B``, ``C`` [T, 2, 256] (head ``h`` reads group
   ``h // 16``); ``dt = softplus(dt + dt_bias)`` [T, 32], ``A = -exp(A_log)``;
   per head a state ``h_t`` [128, 256]: ``h_t = exp(dt_t A) h_{t-1} + dt_t
   x_t B_t^T``, ``y_t = h_t C_t + D x_t``, ``h`` zero before a document's
   first token (:mod:`pathway_tpu.ops.ssd_scan`); ``y = grouped_rmsnorm(y *
   silu(z))`` (the gate, then the norm over each of the 2 groups of 2,048
   channels, times its weight); ``ssm = (y W_out) * ssm_out_multiplier``.
4. ``x = x + attn + ssm``.
5. ``b = rmsnorm(x)``; ``x = x + ((b W_up) * silu((b W_gate) *
   mlp_multipliers[0])) W_down * mlp_multipliers[1]``, width 21,504.
6. after the last layer ``rmsnorm``; a row's vector is the state of its last
   real token (the index normalises it).

A multiplier is applied where this list applies it, never folded into a
weight.

Precision: weights are held in ``param_dtype`` (bfloat16); ``W_in``,
``W_out``, ``Wq/Wk/Wv/Wo``, attention's two products and the MLP take
``dtype`` (bfloat16) operands with float32 accumulation; the residual
stream, norms, rotary, softmax, and everything of the mixer between its two
projections (the convolution, softplus, decays, the products with ``B`` and
``C``, the state, ``D x``, the gate and the gated norm) are float32.

Two layouts over one parameter tree, as the other encoders have them: the
dense forward ([batch, seq] ids and mask, padding behind the text) and the
packed forward (rows end to end on one token axis with segment ids and
positions), which serves (``attention_impl="ragged"``): a flush of documents
is one launch.  A layer with memory meets the packed axis here: attention
keeps documents apart with a mask, the state and the convolution by
resetting at each document's first token, wherever it falls.  Attention,
the norm, rotary and the gated MLP are
:mod:`pathway_tpu.models.causal_moe_embedder`'s.
"""

from __future__ import annotations

import dataclasses
from typing import Any, ClassVar

import jax
import jax.numpy as jnp

from ..ops.ssd_scan import causal_conv, ssd_scan
from .causal_moe_embedder import RotarySpec, _attention, _gated_mlp, _rms_norm, _rotate

__all__ = ["CausalHybridEmbedderConfig", "CausalHybridEmbedder", "init_params"]


@dataclasses.dataclass(frozen=True)
class CausalHybridEmbedderConfig:
    """Falcon-H1-34B's widths by default, at four layers (every layer is of
    one kind)."""

    vocab_size: int = 261_120
    hidden_dim: int = 5120
    num_layers: int = 4
    num_heads: int = 20
    num_kv_heads: int = 4
    head_dim: int = 128
    rotary: RotarySpec = RotarySpec(theta=1e11)
    mlp_dim: int = 21_504
    #: the mixer: heads of ``ssm_head_dim`` channels, a state of
    #: ``ssm_state`` a channel, ``B``/``C`` shared by a group's heads
    ssm_heads: int = 32
    ssm_head_dim: int = 128
    ssm_state: int = 256
    ssm_groups: int = 2
    conv_taps: int = 4
    #: tokens of one chunk of the scan
    chunk: int = 128
    embedding_multiplier: float = 5.656854249492381
    attention_in_multiplier: float = 1.0
    attention_out_multiplier: float = 0.0375
    key_multiplier: float = 0.011048543456039804
    ssm_in_multiplier: float = 0.25
    #: over the zones z, x, B, C, dt of the mixer's input projection
    ssm_multipliers: tuple[float, ...] = (
        0.3535533905932738, 0.25, 0.1767766952966369, 0.5, 0.3535533905932738)
    ssm_out_multiplier: float = 0.08838834764831845
    #: on the gate before its silu, on the MLP's output
    mlp_multipliers: tuple[float, float] = (0.1767766952966369, 0.011160714285714284)
    rms_eps: float = 1e-5
    #: longest row the dispatch takes; rotary positions need no table
    max_len: int = 2048
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.bfloat16
    seq_buckets: tuple[int, ...] = (32, 64, 128, 256, 512, 1024, 2048)
    #: rows of a DENSE launch (``attention_impl="xla"``): one, because each
    #: (rows, sequence) program of a model this size takes seconds to compile
    batch_buckets: tuple[int, ...] = (1,)
    #: queries a block of attention takes at once
    q_block: int = 512
    #: "ragged": a call's rows go out packed along one token axis, as many
    #: to a launch as ``token_buckets[-1]`` tokens hold; "xla": the dense
    #: [batch, seq] dispatch, one row a launch
    attention_impl: str = "ragged"
    #: token counts a packed launch is padded to, each one compiled program;
    #: the last is the most a launch holds, and a call over it goes out as
    #: several launches.  Small even steps and a low top: on a TPU v5e a
    #: launch costs 22.5 us a token of its BUCKET at 768 tokens and 27.3 at
    #: 6,144, padding or not, with no fixed part to speak of (the matrices
    #: are compute-bound from 768 tokens on), so a flush split over two
    #: launches costs what one does and every padded token is lost: four
    #: steps of 768 pad a tenth of what is launched where four of 1,536 up
    #: to 6,144 pad a fifth, for as many programs (PERF.md 6, PR 33)
    token_buckets: tuple[int, ...] = (768, 1536, 2304, 3072)

    program_name: ClassVar[str] = "pw_hybrid_embedder_forward"
    emb_dim: ClassVar[None] = None  # the vector is the hidden state
    #: row counts the packed launch's ``starts`` operand is padded to: one,
    #: so that how many documents share a launch mints no program
    packed_row_buckets: ClassVar[tuple[int, ...]] = (32,)
    #: nothing unpacks to a dense [rows, sequence] shape: rows of any
    #: lengths share a packed launch, which carries no sequence bucket
    packed_unpacks_rows: ClassVar[bool] = False
    #: the first packed dispatch launches every token bucket once on padding
    warm_packed: ClassVar[bool] = True

    def __post_init__(self):
        if self.num_heads % self.num_kv_heads or self.ssm_heads % self.ssm_groups:
            raise ValueError("query heads must be a multiple of num_kv_heads and "
                             "ssm_heads of ssm_groups")
        if len(self.ssm_multipliers) != 5 or len(self.mlp_multipliers) != 2:
            raise ValueError("ssm_multipliers names five zones, mlp_multipliers two places")

    @property
    def ssm_dim(self) -> int:
        return self.ssm_heads * self.ssm_head_dim

    @property
    def ssm_zones(self) -> tuple[int, ...]:
        """Widths of z, x, B, C, dt in the input projection's columns."""
        bc = self.ssm_groups * self.ssm_state
        return (self.ssm_dim, self.ssm_dim, bc, bc, self.ssm_heads)

    def build_models(self):
        return CausalHybridEmbedder(self), CausalHybridEmbedder(self, packed=True)


def _mup_vector(cfg: CausalHybridEmbedderConfig):
    """``ssm_multipliers`` spread over the input projection's columns."""
    return jnp.concatenate([jnp.full((width,), m, jnp.float32)
                            for width, m in zip(cfg.ssm_zones, cfg.ssm_multipliers)])


def _attend_row(cfg: CausalHybridEmbedderConfig, p, a, pos, seg, valid):
    """Step 2 for one token axis: ``a`` [T, D] the normed input (float32) ->
    the attention's addition to the residual, float32."""
    dt = cfg.dtype
    u = (a * cfg.attention_in_multiplier).astype(dt)
    q = jnp.einsum("td,dhe->the", u, p["wq"], preferred_element_type=jnp.float32)
    k = jnp.einsum("td,dhe->the", u, p["wk"], preferred_element_type=jnp.float32)
    v = jnp.einsum("td,dhe->the", u, p["wv"], preferred_element_type=jnp.float32)
    q, k = _rotate(q, pos, cfg.rotary), _rotate(k * cfg.key_multiplier, pos, cfg.rotary)
    o = _attention(q.astype(dt), k.astype(dt), v.astype(dt), pos, seg, valid,
                   window=None, q_block=cfg.q_block)
    return jnp.einsum("the,hed->td", o.astype(dt), p["wo"],
                      preferred_element_type=jnp.float32) * cfg.attention_out_multiplier


def _mix_row(cfg: CausalHybridEmbedderConfig, p, a, pos, seg, valid):
    """Step 3 for one token axis: the mixer's addition to the residual."""
    dt = cfg.dtype
    t = a.shape[0]
    u = (a * cfg.ssm_in_multiplier).astype(dt)
    proj = jnp.dot(u, p["w_in"], preferred_element_type=jnp.float32) * _mup_vector(cfg)
    d, bc = cfg.ssm_dim, cfg.ssm_groups * cfg.ssm_state
    z, xbc, step = proj[:, :d], proj[:, d: 2 * d + 2 * bc], proj[:, 2 * d + 2 * bc:]
    xbc = jax.nn.silu(causal_conv(xbc, p["conv_w"], p["conv_b"], pos))
    f32 = lambda w: w.astype(jnp.float32)
    y = ssd_scan(
        xbc[:, :d].reshape(t, cfg.ssm_heads, cfg.ssm_head_dim),
        jax.nn.softplus(step + f32(p["dt_bias"])), -jnp.exp(f32(p["a_log"])),
        xbc[:, d: d + bc].reshape(t, cfg.ssm_groups, cfg.ssm_state),
        xbc[:, d + bc:].reshape(t, cfg.ssm_groups, cfg.ssm_state),
        f32(p["d"]), seg, pos, valid, chunk=cfg.chunk)
    y = (y.reshape(t, d) * jax.nn.silu(z)).reshape(t, cfg.ssm_groups, d // cfg.ssm_groups)
    y = y * jax.lax.rsqrt(jnp.mean(y * y, axis=-1, keepdims=True) + cfg.rms_eps)
    y = (y.reshape(t, d) * f32(p["norm"])).astype(dt)
    return jnp.dot(y, p["w_out"], preferred_element_type=jnp.float32) * cfg.ssm_out_multiplier


def _layer(cfg: CausalHybridEmbedderConfig, p, x, pos, seg, valid):
    """One block: ``x`` [B, T, D] float32.  ``seg`` None: each of the B rows
    is one text with its padding behind it; else B is 1 and the row is texts
    packed end to end."""
    a = _rms_norm(x, p["attn_norm"], cfg.rms_eps)

    def branches(a_, pos_, seg_, valid_):
        return (_attend_row(cfg, p, a_, pos_, seg_, valid_),
                _mix_row(cfg, p, a_, pos_, seg_, valid_))

    if seg is None:
        attn, ssm = jax.vmap(lambda a_, pos_, valid_: branches(a_, pos_, None, valid_))(
            a, pos, valid)
    else:
        attn, ssm = (branch[None] for branch in branches(a[0], pos[0], seg[0], valid[0]))
    x = x + attn + ssm  # in this order: a float32 sum taken in another rounds another way
    b = _rms_norm(x, p["mlp_norm"], cfg.rms_eps).astype(cfg.dtype)
    gate_scale, out_scale = cfg.mlp_multipliers
    return x + _gated_mlp(b, p["w_gate_up"], p["w_down"], gate_scale=gate_scale) * out_scale


def _tokens_forward(cfg, params, ids, pos, seg, valid):
    """Blocks and final norm: ``ids``, ``pos``, ``valid`` [B, T], ``seg``
    [B, T] or None (a row is one text) -> [B, T, D] float32."""
    x = params["tok_emb"][ids].astype(jnp.float32) * cfg.embedding_multiplier
    for i in range(cfg.num_layers):
        x = _layer(cfg, params[f"layer_{i}"], x, pos, seg, valid)
    return _rms_norm(x, params["final_norm"], cfg.rms_eps)


def _counters(documents, valid):
    """int32 [4] of one launch: launches (1), documents, real tokens, the
    tokens of its bucket (``flight_recorder.record_ssm_launch`` adds them up)."""
    return jnp.stack([jnp.int32(1), jnp.sum(documents), jnp.sum(valid),
                      jnp.int32(valid.size)]).astype(jnp.int32)


class CausalHybridEmbedder:
    """The model as :class:`SentenceEncoder` takes one: ``init`` and
    ``apply`` over ``{"params": tree}``.  ``apply`` returns (vectors float32,
    the launch's counters); ``record_launch`` is where the encoder sends the
    second."""

    def __init__(self, cfg: CausalHybridEmbedderConfig, packed: bool = False):
        self.cfg = cfg
        self.packed = packed

    @staticmethod
    def record_launch(counters) -> None:
        from ..internals.flight_recorder import record_ssm_launch

        record_ssm_launch(counters)

    def init(self, key, *_example):
        return {"params": init_params(self.cfg, key)}

    def layer(self, layer_params, i: int, x, seg=None, pos=None):
        """Block ``i`` alone over one token axis of states ``x`` [T, D] ->
        [T, D] float32: what a check calls to feed the program a reference's
        own input to that block (every block is of one kind, so ``i`` picks
        nothing but is what the check hands over).  ``seg`` and ``pos`` None:
        one text, every token real.  Else texts end to end as a flush packs
        them: ``seg`` [T] names each token's text (negative: padding) and
        ``pos`` [T] its position in it."""
        t = x.shape[0]
        x = jnp.asarray(x, jnp.float32)[None]
        if seg is None:
            return _layer(self.cfg, layer_params, x, jnp.arange(t)[None], None,
                          jnp.ones((1, t), bool))[0]
        seg, pos = jnp.asarray(seg, jnp.int32), jnp.asarray(pos, jnp.int32)
        return _layer(self.cfg, layer_params, x, pos[None], seg[None], (seg >= 0)[None])[0]

    def apply(self, variables, *args, **kwargs):
        params = variables["params"]
        if self.packed:
            return self._apply_packed(params, *args, **kwargs)
        return self._apply_dense(params, *args)

    def _apply_dense(self, params, ids, mask):
        """[B, S] ids and mask (padding behind the text) -> [B, D]."""
        ids = ids.astype(jnp.int32)
        mask = mask.astype(jnp.int32)
        valid = mask > 0
        b, s = ids.shape
        pos = jnp.broadcast_to(jnp.arange(s), (b, s))
        x = _tokens_forward(self.cfg, params, ids, pos, None, valid)
        lengths = jnp.sum(mask, axis=1)
        return x[jnp.arange(b), jnp.maximum(lengths - 1, 0)], _counters(lengths > 0, valid)

    def _apply_packed(self, params, ids, pos, seg, starts, bounds=None, *,
                      dense_s: int | None = None):
        """Rows concatenated along one token axis (``ragged_prepare``):
        ``seg`` names each token's row, the pad tail carries ``seg == rows``;
        ``starts`` [rows] is where each row begins (rows past the launch's
        own begin at 0 and hold no token).  ``bounds`` and ``dense_s`` serve
        the BERT encoder's kernel and are not read."""
        ids, pos, seg = (jnp.asarray(a, jnp.int32) for a in (ids, pos, seg))
        rows = starts.shape[0]
        valid = seg < rows
        x = _tokens_forward(self.cfg, params, ids[None], pos[None], seg[None], valid[None])
        lengths = jnp.zeros((rows + 1,), jnp.int32).at[seg].add(1)[:rows]
        last = starts.astype(jnp.int32) + jnp.maximum(lengths - 1, 0)
        return x[0][last], _counters(lengths > 0, valid)


def init_params(cfg: CausalHybridEmbedderConfig, key):
    """A parameter tree drawn layer by layer (a layer is 0.86 GB): token
    embeddings at unit scale, matrices at 1/sqrt(fan-in), norms and ``D`` at
    one, the time steps' bias and ``A_log`` as Mamba-2 draws them (steps of
    0.001 to 0.1, ``A`` of -1 to -16)."""
    pd, d, hd = cfg.param_dtype, cfg.hidden_dim, cfg.head_dim
    h, kv, taps = cfg.num_heads, cfg.num_kv_heads, cfg.conv_taps
    conv_dim = cfg.ssm_dim + 2 * cfg.ssm_groups * cfg.ssm_state

    def normal(key, shape, fan_in):
        return (jax.random.normal(key, shape, jnp.float32) * fan_in ** -0.5).astype(pd)

    keys = jax.random.split(key, cfg.num_layers + 1)
    params = {"tok_emb": jax.random.normal(keys[0], (cfg.vocab_size, d), jnp.float32).astype(pd),
              "final_norm": jnp.ones((d,), pd)}
    for i in range(cfg.num_layers):
        k = jax.random.split(keys[i + 1], 12)
        step = jnp.exp(jax.random.uniform(k[8], (cfg.ssm_heads,), jnp.float32,
                                          jnp.log(0.001), jnp.log(0.1)))
        params[f"layer_{i}"] = {
            "attn_norm": jnp.ones((d,), pd), "mlp_norm": jnp.ones((d,), pd),
            "wq": normal(k[0], (d, h, hd), d), "wk": normal(k[1], (d, kv, hd), d),
            "wv": normal(k[2], (d, kv, hd), d), "wo": normal(k[3], (h, hd, d), h * hd),
            "w_in": normal(k[4], (d, sum(cfg.ssm_zones)), d),
            "conv_w": normal(k[5], (taps, conv_dim), taps),
            "conv_b": normal(k[6], (conv_dim,), 100),
            "w_out": normal(k[7], (cfg.ssm_dim, d), cfg.ssm_dim),
            "dt_bias": (step + jnp.log(-jnp.expm1(-step))).astype(pd),  # softplus^-1
            "a_log": jnp.log(jax.random.uniform(k[9], (cfg.ssm_heads,), jnp.float32,
                                                1.0, 16.0)).astype(pd),
            "d": jnp.ones((cfg.ssm_heads,), pd), "norm": jnp.ones((cfg.ssm_dim,), pd),
            "w_gate_up": normal(k[10], (d, 2 * cfg.mlp_dim), d),
            "w_down": normal(k[11], (cfg.mlp_dim, d), cfg.mlp_dim),
        }
    return params
