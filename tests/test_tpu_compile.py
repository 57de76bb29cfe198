"""Programs of the language-model embedder's cell compiled for a TPU v5e that
is described, not attached (the chip's own compiler is installed here): what
it refuses costs no chip time.  Nothing runs, so nothing here is a result or a
time.

Why tier-1 holds them: the CPU tests run these kernels in interpret mode,
which knows nothing of the chip's fast memory or of what fits its HBM.  The
cell's first chip run ended in ``RESOURCE_EXHAUSTED`` in ``vmem`` in the
search megakernel at rows of 2,048 values, after every interpret-mode test
had passed; a later PR that touches that kernel's blocks, or the embedder's
shapes, would find out the same way, on the chip's budget.  Three compiles,
one file (the on-chip-measurement guide, section 2): the topology is described
inside a fixture of THIS file only, because one process at a time may load the
TPU's library, and the persistent compile cache is kept out of it, because a
compile for a described chip cannot be read back without the chip."""

from __future__ import annotations

import json
import os
import sys

import jax
import jax.numpy as jnp
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "perfbench")


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")  # or the compiler logs under /tmp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - no compiler here: nothing to guard
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture()
def no_persistent_cache():
    """A compile for a described chip is written to the cache and cannot
    be read back without the chip: keep it out."""
    from jax.experimental.compilation_cache import compilation_cache

    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", before)
    compilation_cache.reset_cache()


def test_the_search_megakernel_compiles_for_rows_of_2048_values(one_chip, no_persistent_cache):
    """1,024 rows of 2,048 float32 values are 8 MiB a block: the kernel has
    to ask for its VMEM (the first chip run of the cell ended in
    RESOURCE_EXHAUSTED in ``vmem`` here)."""
    from pathway_tpu.ops import fused_serving as fs

    n, d = 262_144, 2048
    block = fs.validate_serving_geometry(n, "cos")
    fn = getattr(fs._pallas_fused_dense, "__wrapped__", fs._pallas_fused_dense)
    for q_b, q_dtype in ((8, jnp.bfloat16), (256, jnp.float32)):
        args = (jax.ShapeDtypeStruct((q_b, d), q_dtype, sharding=one_chip),
                jax.ShapeDtypeStruct((n, d), jnp.float32, sharding=one_chip),
                jax.ShapeDtypeStruct((n,), jnp.bool_, sharding=one_chip))
        compiled = fn.lower(*args, k=16, q_b=q_b, metric="cos", normalize=True, qdt="f32",
                            block_n=block, interpret=False).compile()
        assert "tpu_custom_call" in compiled.as_text()


def _published_model(one_chip):
    """The cell's config object and its parameter tree as shapes on the
    described chip."""
    if BENCH not in sys.path:
        sys.path.insert(0, BENCH)
    from encoders import laguna as builder

    from pathway_tpu.models import causal_moe_embedder as cme

    with open(os.path.join(BENCH, "configs", "vs-laguna-xs2-bf16-marcodoc.json")) as f:
        config = json.load(f)
    cfg = builder.model_config(config)
    shapes = jax.eval_shape(lambda: cme.init_params(cfg, jax.random.PRNGKey(0)))
    return cfg, jax.tree_util.tree_map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one_chip), shapes)


def test_the_forward_compiles_at_the_published_widths_and_fits_the_chip(
        one_chip, no_persistent_cache):
    from pathway_tpu.models import causal_moe_embedder as cme

    cfg, params = _published_model(one_chip)
    model = cme.CausalMoeEmbedder(cfg)
    ids = jax.ShapeDtypeStruct((1, 128), jnp.int32, sharding=one_chip)
    mask = jax.ShapeDtypeStruct((1, 128), jnp.uint8, sharding=one_chip)
    compiled = jax.jit(lambda p, i, m: model.apply({"params": p}, i, m)).lower(
        params, ids, mask).compile()
    memory = compiled.memory_analysis()
    assert memory.argument_size_in_bytes == pytest.approx(7.33e9, rel=0.01)  # bfloat16
    assert memory.argument_size_in_bytes + memory.temp_size_in_bytes < 16e9
    # the grouped product is XLA's own TPU kernel, not a dense product over
    # 256 experts: the trace names it ragged-dot
    assert "ragged-dot" in compiled.as_text()


def test_the_packed_forward_compiles_at_its_largest_token_bucket_beside_the_index(
        one_chip, no_persistent_cache):
    """The launch that serves: the largest token bucket, the arrays as
    ``ragged_chunk`` lays them out.  Its temporaries (eight routed rows a
    token) have to fit beside the weights and the three copies of the
    index's 2.15 GB that an apply holds."""
    import numpy as np

    from pathway_tpu.models import causal_moe_embedder as cme
    from pathway_tpu.models.encoder import dispatch_dtype, ragged_chunk

    cfg, params = _published_model(one_chip)
    none = np.zeros(0, np.int64)
    chunk = ragged_chunk(none, none, None, None, cfg.max_len, dispatch_dtype(cfg.vocab_size),
                         cfg, tokens=cfg.token_buckets[-1])
    assert chunk.ids.shape == (cfg.token_buckets[-1],) and chunk.dense_s is None
    args = [jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip)
            for a in (chunk.ids, chunk.pos, chunk.seg, chunk.starts)]
    model = cme.CausalMoeEmbedder(cfg, packed=True)
    compiled = jax.jit(lambda p, *a: model.apply({"params": p}, *a)).lower(
        params, *args).compile()
    memory = compiled.memory_analysis()
    assert memory.argument_size_in_bytes == pytest.approx(7.33e9, rel=0.01)
    assert memory.argument_size_in_bytes + memory.temp_size_in_bytes + 3 * 2.15e9 < 15.75e9
    assert "ragged-dot" in compiled.as_text()
