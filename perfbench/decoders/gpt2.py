"""The GPT-2 decoder of a configuration's ``decoder`` group: its weights from
the seed, the program's chat object that serves it, and the sizes the cost
functions need.  A configuration names this file (``"decoder": {"builder":
"gpt2", ...}``); another architecture brings a file of its own beside it and
a reference under ``checks/`` (``"reference"``), and edits nothing here.

Nothing of the program is imported at the top: the check reads ``params``
and ``sizes`` too, and the reference may take nothing the program has made.
"""

from __future__ import annotations

import seeded

KEYS = {  # configuration key (HF name) -> DecoderConfig argument
    "vocab_size": "vocab_size", "n_embd": "hidden_dim", "n_layer": "num_layers",
    "n_head": "num_heads", "n_inner": "mlp_dim", "n_positions": "max_len",
    "layer_norm_epsilon": "ln_eps",
}

#: the jitted programs of ``generation/engine.py`` as a device trace names them
PROGRAMS = {"prefill": ("jit__paged_prefill_impl",),
            "decode_step": ("jit__paged_step_impl",),
            "verify": ("jit__paged_multi_step_impl",)}


def params(config: dict, seed: int):
    """The decoder's weights, made on the device in one jitted call."""
    d = config["decoder"]
    return seeded.gpt2_params(seeded.key_of(seed, 2), vocab=d["vocab_size"],
                              hidden=d["n_embd"], layers=d["n_layer"], ffn=d["n_inner"],
                              positions=d["n_positions"])


def chat(config: dict):
    """The program's chat object over this decoder.  The model itself is
    made at ``_ensure_lm()`` (the program's own lazy path), so that a server
    can bring its index up before the decoder takes its memory."""
    from pathway_tpu.models.decoder import DecoderConfig
    from pathway_tpu.xpacks.llm.llms import JaxPipelineChat

    d = config["decoder"]
    return JaxPipelineChat(model=None, cfg=DecoderConfig(**{KEYS[k]: d[k] for k in KEYS}))


def sizes(config: dict) -> dict:
    """What ``costs.py`` needs of this architecture, from the configuration
    alone: ``matrix_params`` are the parameters every step multiplies by (the
    four matrices of each block and the tied output head; the position table
    and the norms are looked up or are thousands), ``kv_values_per_token``
    the keys and values one cached position holds over all layers."""
    d = config["decoder"]
    h, f = d["n_embd"], d["n_inner"]
    return {"hidden": h, "layers": d["n_layer"], "ffn": f, "vocab": d["vocab_size"],
            "matrix_params": d["n_layer"] * (4 * h * h + 2 * h * f) + d["vocab_size"] * h,
            "kv_values_per_token": d["n_layer"] * 2 * h}
