"""Routed experts: a float32 router, tokens grouped by expert without
dropping any, one grouped matrix product over the experts that got tokens,
the weighted combine.

The layer of a sparse mixture-of-experts block with either of two scorings
(:func:`route`): the Qwen-MoE lineage's (softmax scores over all experts, the
``top_k`` largest kept, their scores renormalised and scaled) and the
DeepSeek-V3 lineage's (``noaux_tc``: sigmoid scores, the ``top_k`` largest of
score + a per-expert bias chosen, the weights the UNBIASED scores of the
chosen, renormalised and scaled), for a flat axis of tokens:

* every token is routed and computed: there is no capacity and no drop, so
  a launch of several documents gives each the result it gets alone;
* a token that is not ``valid`` (padding) is routed nowhere: it belongs to
  no group of the grouped product, reads no expert's weights and gets 0;
* the layer may hold a share of the experts (expert parallelism: the
  others lie on other chips): ``w_gate_up``/``w_down`` hold ``E_held``
  experts from ``first_expert`` on while the router scores all of them; a
  token is routed and weighted over all, and a pair whose expert is held
  elsewhere goes with the padding, reads no weights and adds 0, so the layer
  gives its own experts' part of the result.  Nothing here stands in for the
  other chips or for the exchange with them;
* the grouped product is :func:`pathway_tpu.ops.grouped_matmul.grouped_matmul`:
  on a TPU the Pallas kernel ``pw_grouped_matmul``, which reads each expert
  that got a row once a call, visits no row past the groups' total and
  writes the gated activation ``silu(x Wg) * (x Wu)`` in the compute dtype
  from the first product; elsewhere its XLA twin, :func:`jax.lax.ragged_dot`
  with the same epilogue after it (``ragged-dot`` in a device trace);
* the router's scores, the choice and the weights are float32 at
  ``highest`` precision whatever the weights are held in: a score rounded to
  bfloat16 flips an expert wherever two lie within 2^-8 of each other.

The launch's counters (:func:`launch_counters`) are computed here, from the
group sizes, and ride back with the forward's result.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from .grouped_matmul import grouped_matmul

__all__ = ["route", "group_tokens", "held_pairs", "grouped_matmul", "routed_experts",
           "launch_counters"]


def route(x, router, *, top_k: int, scaling: float, scoring: str = "softmax",
          bias=None, eps: float = 1e-20):
    """``x`` [T, D] (any float dtype), ``router`` [D, E] -> the chosen
    experts [T, top_k] (int32, by falling score) and their weights
    [T, top_k] (float32), all in float32.

    ``scoring="softmax"``: softmax over all E experts, the ``top_k``
    largest, divided by their sum, times ``scaling``.
    ``scoring="sigmoid"``: a sigmoid an expert; divided by the chosen's sum
    + ``eps`` (1e-20 in the DeepSeek-V3 lineage), times ``scaling``.
    ``bias`` [E] (``e_score_correction_bias``) enters the CHOICE and not the
    weight: the ``top_k`` largest of score + bias are chosen (listed by
    falling biased score), and weighted by their scores without it.  The
    lineage's group limit (the best ``topk_group`` of ``n_group`` groups of
    experts may be chosen from) is not coded: with one group it keeps every
    expert, and no configuration here has more."""
    logits = jnp.dot(
        x.astype(jnp.float32), router.astype(jnp.float32),
        precision=jax.lax.Precision.HIGHEST,
    )
    if scoring not in ("softmax", "sigmoid"):
        raise ValueError(f"scoring {scoring!r}")
    soft = scoring == "softmax"
    scores = jax.nn.softmax(logits, axis=-1) if soft else jax.nn.sigmoid(logits)
    if bias is None:
        top, experts = jax.lax.top_k(scores, top_k)
    else:
        _, experts = jax.lax.top_k(scores + bias.astype(jnp.float32), top_k)
        top = jnp.take_along_axis(scores, experts, axis=-1)
    total = jnp.sum(top, axis=-1, keepdims=True)
    return experts.astype(jnp.int32), top / (total if soft else total + eps) * scaling


def held_pairs(experts, valid, num_experts: int, first_expert: int = 0):
    """[T, K] bool: the pairs of real tokens whose expert is among the
    ``num_experts`` held from ``first_expert`` on."""
    return valid[:, None] & (experts >= first_expert) & (experts < first_expert + num_experts)


def group_tokens(experts, valid, num_experts: int, first_expert=None):
    """The routed (token, expert) pairs sorted by expert.

    ``experts`` [T, K], ``valid`` [T] bool; ``first_expert`` None: all
    ``num_experts`` are held, else ``num_experts`` from ``first_expert`` on
    and a pair of another expert goes with the padding.  Returns ``order``
    [T*K] (the pairs by held expert, the others last), ``group_sizes``
    [num_experts] (valid pairs of each held expert) and ``inverse`` [T*K]
    (where each pair went)."""
    t, k = experts.shape
    if first_expert is None:
        flat = jnp.where(valid[:, None], experts, num_experts).reshape(t * k)
    else:
        flat = jnp.where(held_pairs(experts, valid, num_experts, first_expert),
                         experts - first_expert, num_experts).reshape(t * k)
    order = jnp.argsort(flat, stable=True)
    group_sizes = jnp.zeros((num_experts + 1,), jnp.int32).at[flat].add(1)[:num_experts]
    inverse = jnp.zeros((t * k,), jnp.int32).at[order].set(
        jnp.arange(t * k, dtype=jnp.int32))
    return order, group_sizes, inverse


def routed_experts(x, valid, router, w_gate_up, w_down, *, top_k: int,
                   scaling: float, router_input=None, scoring: str = "softmax",
                   bias=None, eps: float = 1e-20, first_expert: int = 0):
    """Sum over each token's experts of ``weight * E(x)`` with ``E`` a gated
    MLP (``silu(x Wg) * (x Wu)) Wd``.

    ``x`` [T, D] in the compute dtype, ``valid`` [T] bool, ``router``
    [D, E], ``w_gate_up`` [E_held, D, 2F] (gate columns first), ``w_down``
    [E_held, F, D], experts ``first_expert..first_expert + E_held - 1`` of
    the E; ``router_input`` is what the router reads where ``x`` is a
    rounded copy of it; ``scoring``, ``bias`` [E] and ``eps`` are
    :func:`route`'s.
    Returns ([T, D] float32: the held experts' part, group sizes [E_held])."""
    num_experts = w_gate_up.shape[0]
    share = first_expert != 0 or num_experts != router.shape[1]
    experts, weights = route(x if router_input is None else router_input, router,
                             top_k=top_k, scaling=scaling, scoring=scoring, bias=bias,
                             eps=eps)
    order, group_sizes, inverse = group_tokens(experts, valid, num_experts,
                                               first_expert if share else None)
    rows = x[order // top_k]
    act = grouped_matmul(rows, w_gate_up, group_sizes, gated=True)
    y = grouped_matmul(act, w_down, group_sizes)
    y = y[inverse].reshape(experts.shape + (y.shape[-1],))
    keep = (held_pairs(experts, valid, num_experts, first_expert)[:, :, None] if share
            else valid[:, None, None])
    out = jnp.sum(jnp.where(keep, y * weights[:, :, None], 0.0), axis=1)
    return out, group_sizes


def launch_counters(group_sizes: list):
    """int32 [4] of one launch from the group sizes [E] of its routed
    layers: token-expert pairs routed, experts that got a token (both
    summed over the layers), each layer's fullest expert summed, the
    fullest of all (``flight_recorder.record_moe_launch`` adds them up)."""
    sizes = jnp.stack(group_sizes)
    fullest = jnp.max(sizes, axis=1)
    return jnp.stack([
        jnp.sum(sizes), jnp.sum(sizes > 0), jnp.sum(fullest), jnp.max(fullest),
    ]).astype(jnp.int32)
