"""Programs of the language-model embedder's cell compiled for a TPU v5e that
is described, not attached (the chip's own compiler is installed here): what
it refuses costs no chip time.  Nothing runs, so nothing here is a result or a
time.

Why tier-1 holds them: the CPU tests run these kernels in interpret mode,
which knows nothing of the chip's fast memory or of what fits its HBM.  The
cell's first chip run ended in ``RESOURCE_EXHAUSTED`` in ``vmem`` in the
search megakernel at rows of 2,048 values, after every interpret-mode test
had passed; a later PR that touches that kernel's blocks, or the embedder's
shapes, would find out the same way, on the chip's budget.  The hybrid
embedder's cell (``ingest-docs-falcon-h1``) added its own: the scan kernel at
the published shapes, the packed forward at each token bucket with that
kernel in it, and the index's search and apply at rows of 5,120 values.  The
latent-attention embedder's cell (``ingest-docs-joyai``), the fullest, its
packed forward at each token bucket beside 10.59 GB of weights and the index
at its shapes; the conv embedder's cell (``ingest-docs-lfm2``) its packed
forward at each token bucket beside 10.53 GB.  The routed embedders' forwards
are compiled with the grouped product's Pallas kernel in them, as a TPU runs
them, and the kernel alone at the three cells' shapes.  Some thirty compiles,
one file: the topology is described
inside a fixture of THIS file only, because one process at a time may load the
TPU's library, and the persistent compile cache is kept out of it, because a
compile for a described chip cannot be read back without the chip."""

from __future__ import annotations

import functools
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


def _cell_model(one_chip, builder_name: str, config_name: str, model_module):
    """A cell's configuration file, its config object and its parameter tree
    as shapes on the described chip."""
    import importlib

    if BENCH not in sys.path:
        sys.path.insert(0, BENCH)
    builder = importlib.import_module("encoders." + builder_name)
    with open(os.path.join(BENCH, "configs", config_name + ".json")) as f:
        config = json.load(f)
    cfg = builder.model_config(config)
    shapes = jax.eval_shape(lambda: model_module.init_params(cfg, jax.random.PRNGKey(0)))
    return config, cfg, jax.tree_util.tree_map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one_chip), shapes)


def _the_chip_s_grouped_product(monkeypatch):
    """The model asks the platform, which is the CPU here: hand it the
    grouped product's kernel, as a TPU would."""
    from pathway_tpu.ops import grouped_matmul as GM

    monkeypatch.setattr(GM, "grouped_matmul_impl", lambda: "pallas")


def _assert_the_kernel_and_no_ragged_dot(compiled):
    from pathway_tpu.ops import grouped_matmul as GM

    text = compiled.as_text()
    assert GM.KERNEL_NAME in text and "ragged-dot" not in text


@pytest.mark.parametrize("rows", [4096 * 4, 6144 * 8, 937 * 4])
@pytest.mark.parametrize("expert_width,experts", [(1536, 64), (768, 256), (512, 256)],
                         ids=["lfm2", "joyai", "laguna"])
def test_the_grouped_product_kernel_compiles_at_the_three_cells_shapes(
        one_chip, no_persistent_cache, expert_width, experts, rows):
    """Both products of a routed layer over a hidden of 2,048: the gated one
    (two blocks of the expert's [2,048, 2 x width] a group in fast memory)
    and the plain one, at a launch's rows and at a check's odd row count."""
    from pathway_tpu.ops import grouped_matmul as GM

    shape = lambda s, t: jax.ShapeDtypeStruct(s, t, sharding=one_chip)
    for gated, (k, n) in ((True, (2048, 2 * expert_width)), (False, (expert_width, 2048))):
        compiled = jax.jit(functools.partial(GM.grouped_matmul_pallas, gated=gated)).lower(
            shape((rows, k), jnp.bfloat16), shape((experts, k, n), jnp.bfloat16),
            shape((experts,), jnp.int32)).compile()
        assert "tpu_custom_call" in compiled.as_text()
        assert compiled.memory_analysis().temp_size_in_bytes < 1e8


def _published_model(one_chip):
    from pathway_tpu.models import causal_moe_embedder as cme

    return _cell_model(one_chip, "laguna", "vs-laguna-xs2-bf16-marcodoc", cme)[1:]


def test_the_forward_compiles_at_the_published_widths_and_fits_the_chip(
        one_chip, no_persistent_cache, monkeypatch):
    from pathway_tpu.models import causal_moe_embedder as cme

    _the_chip_s_grouped_product(monkeypatch)
    cfg, params = _published_model(one_chip)
    model = cme.CausalMoeEmbedder(cfg)
    ids = jax.ShapeDtypeStruct((1, 128), jnp.int32, sharding=one_chip)
    mask = jax.ShapeDtypeStruct((1, 128), jnp.uint8, sharding=one_chip)
    compiled = jax.jit(lambda p, i, m: model.apply({"params": p}, i, m)).lower(
        params, ids, mask).compile()
    memory = compiled.memory_analysis()
    assert memory.argument_size_in_bytes == pytest.approx(7.33e9, rel=0.01)  # bfloat16
    assert memory.argument_size_in_bytes + memory.temp_size_in_bytes < 16e9
    # the grouped product is the repo's kernel, not a dense product over
    # 256 experts
    _assert_the_kernel_and_no_ragged_dot(compiled)


def test_the_packed_forward_compiles_at_its_largest_token_bucket_beside_the_index(
        one_chip, no_persistent_cache, monkeypatch):
    """The launch that serves: the largest token bucket, the arrays as
    ``ragged_chunk`` lays them out.  Its temporaries (eight routed rows a
    token) have to fit beside the weights and the three copies of the
    index's 2.15 GB that an apply holds."""
    import numpy as np

    from pathway_tpu.models import causal_moe_embedder as cme
    from pathway_tpu.models.encoder import dispatch_dtype, ragged_chunk

    _the_chip_s_grouped_product(monkeypatch)
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
    _assert_the_kernel_and_no_ragged_dot(compiled)


# -- the hybrid (attention + state-space) embedder's cell -----------------------

def _hybrid_model(one_chip):
    from pathway_tpu.models import causal_hybrid_embedder as che

    return _cell_model(one_chip, "falcon_h1", "vs-falcon-h1-34b-bf16-marcodoc", che)


@pytest.mark.parametrize("tokens", [768, 3072, 6144])
def test_the_scan_kernel_compiles_at_the_published_shapes(one_chip, no_persistent_cache, tokens):
    """32 heads of 128 channels, a state of 256, 2 groups, chunks of 128:
    interpret mode knows nothing of Mosaic's tiles or of VMEM."""
    from pathway_tpu.ops import ssd_scan as S

    h, p, g, n, chunk = 32, 128, 2, 256, 128
    shape = lambda s, d=jnp.float32: jax.ShapeDtypeStruct(s, d, sharding=one_chip)
    args = (shape((tokens, h, p)), shape((tokens, h)), shape((h,)), shape((tokens, g, n)),
            shape((tokens, g, n)), shape((h,)), shape((tokens,), jnp.int32),
            shape((tokens,), jnp.int32), shape((tokens,), jnp.bool_))
    compiled = jax.jit(lambda *a: S.ssd_scan_pallas(*a, chunk=chunk)).lower(*args).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text and S.KERNEL_NAME in text


def test_the_hybrid_packed_forward_compiles_at_each_token_bucket_beside_the_index(
        one_chip, no_persistent_cache, monkeypatch):
    """The launches that serve, the arrays as ``ragged_chunk`` lays them out,
    with the scan's kernel in them (the model asks the platform, which is the
    CPU here: the test hands it the kernel).  The temporaries (the MLP's
    [tokens, 43,008] float32 product first) have to fit beside 6.11 GB of
    weights and the three copies of the index's 1.34 GB that an apply holds."""
    import numpy as np

    from pathway_tpu.models import causal_hybrid_embedder as che
    from pathway_tpu.models.encoder import dispatch_dtype, ragged_chunk
    from pathway_tpu.ops import ssd_scan as S

    monkeypatch.setattr(che, "ssd_scan", S.ssd_scan_pallas)
    _config, cfg, params = _hybrid_model(one_chip)
    model = che.CausalHybridEmbedder(cfg, packed=True)
    none = np.zeros(0, np.int64)
    for tokens in cfg.token_buckets:
        chunk = ragged_chunk(none, none, None, None, cfg.max_len,
                             dispatch_dtype(cfg.vocab_size), cfg, tokens=tokens)
        assert chunk.ids.shape == (tokens,) and chunk.dense_s is None
        args = [jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip)
                for a in (chunk.ids, chunk.pos, chunk.seg, chunk.starts)]
        compiled = jax.jit(lambda p, *a: model.apply({"params": p}, *a)).lower(
            params, *args).compile()
        memory = compiled.memory_analysis()
        assert memory.argument_size_in_bytes == pytest.approx(6.115e9, rel=0.01)  # bfloat16
        assert (memory.argument_size_in_bytes + memory.temp_size_in_bytes
                + 3 * 1.342e9 < 15.75e9), tokens
        assert S.KERNEL_NAME in compiled.as_text()


def test_the_check_s_packed_layer_compiles_at_the_published_widths(
        one_chip, no_persistent_cache, monkeypatch):
    """What ``layer_gap`` calls in the cell: one block over the longest
    document behind a neighbour on a packed axis (2,283 tokens: eighteen
    chunks, five query blocks), with the scan's kernel in it."""
    from pathway_tpu.models import causal_hybrid_embedder as che
    from pathway_tpu.ops import ssd_scan as S

    monkeypatch.setattr(che, "ssd_scan", S.ssd_scan_pallas)
    config, cfg, params = _hybrid_model(one_chip)  # puts perfbench/ on the path
    from encoders import falcon_h1 as builder

    x = jax.ShapeDtypeStruct((cfg.max_len, cfg.hidden_dim), jnp.float32, sharding=one_chip)
    program = builder._layer_program(json.dumps(config, sort_keys=True), 0)
    compiled = program.lower(params["layer_0"], x).compile()
    assert S.KERNEL_NAME in compiled.as_text()
    assert compiled.memory_analysis().temp_size_in_bytes < 1e9


def test_the_index_searches_and_applies_rows_of_5120_values(one_chip, no_persistent_cache):
    """65,536 slots of 5,120 float32 values: a block of 1,024 rows is 20 MiB,
    which the megakernel has to ask VMEM for; the apply is the scatter of a
    flush's rows (32 at most a launch) and of their validity."""
    from pathway_tpu.ops import fused_serving as fs
    from pathway_tpu.ops import knn

    config, _cfg, _params = _hybrid_model(one_chip)
    n, d = config["index"]["capacity"], config["index"]["dim"]
    assert (n, d) == (65_536, 5120)
    shape = lambda s, t: jax.ShapeDtypeStruct(s, t, sharding=one_chip)
    block = fs.validate_serving_geometry(n, "cos")
    fn = getattr(fs._pallas_fused_dense, "__wrapped__", fs._pallas_fused_dense)
    for q_b, q_dtype in ((8, jnp.bfloat16), (32, jnp.float32)):
        compiled = fn.lower(shape((q_b, d), q_dtype), shape((n, d), jnp.float32),
                            shape((n,), jnp.bool_), k=16, q_b=q_b, metric="cos",
                            normalize=True, qdt="f32", block_n=block, interpret=False).compile()
        assert "tpu_custom_call" in compiled.as_text()
    scatter = getattr(knn._scatter_rows_dropping, "__wrapped__", knn._scatter_rows_dropping)
    compiled = scatter.lower(shape((n, d), jnp.float32), shape((32,), jnp.int32),
                             shape((32, d), jnp.float32), normalize=True).compile()
    memory = compiled.memory_analysis()
    # undonated: the matrix in, the matrix out (PERF.md 7 #6), beside the weights
    assert memory.argument_size_in_bytes + memory.output_size_in_bytes < 2 * 1.35e9
    mask = getattr(knn._scatter_mask, "__wrapped__", knn._scatter_mask)
    mask.lower(shape((n,), jnp.bool_), shape((32,), jnp.int32),
               shape((32,), jnp.bool_)).compile()


# -- the latent-attention embedder's cell ---------------------------------------

def _latent_model(one_chip):
    from pathway_tpu.models import causal_moe_embedder as cme

    return _cell_model(one_chip, "joyai", "vs-joyai-flash-bf16-marcodoc", cme)


def test_the_latent_packed_forward_compiles_at_each_token_bucket_beside_the_index(
        one_chip, no_persistent_cache, monkeypatch):
    """The fullest cell: 10.59 GB of weights (every one of 256 experts of 768
    in four layers, the whole vocabulary).  Each launch that serves, the
    arrays as ``ragged_chunk`` lays them out: its temporaries (eight routed
    rows a token, 32 heads of 192 + 128 a token) have to fit beside the
    weights and the three copies of the index's 0.54 GB that an apply holds."""
    import numpy as np

    from pathway_tpu.models import causal_moe_embedder as cme
    from pathway_tpu.models.encoder import dispatch_dtype, ragged_chunk

    _the_chip_s_grouped_product(monkeypatch)
    _config, cfg, params = _latent_model(one_chip)
    assert set(cfg.layer_types) == {"latent"} and len(cfg.token_buckets) == 4
    model = cme.CausalMoeEmbedder(cfg, packed=True)
    none = np.zeros(0, np.int64)
    for tokens in cfg.token_buckets:
        chunk = ragged_chunk(none, none, None, None, cfg.max_len,
                             dispatch_dtype(cfg.vocab_size), cfg, tokens=tokens)
        assert chunk.ids.shape == (tokens,) and chunk.dense_s is None
        args = [jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip)
                for a in (chunk.ids, chunk.pos, chunk.seg, chunk.starts)]
        compiled = jax.jit(lambda p, *a: model.apply({"params": p}, *a)).lower(
            params, *args).compile()
        memory = compiled.memory_analysis()
        assert memory.argument_size_in_bytes == pytest.approx(10.587e9, rel=0.01)  # bfloat16
        assert (memory.argument_size_in_bytes + memory.temp_size_in_bytes
                + 3 * 0.537e9 < 15.75e9), tokens
        _assert_the_kernel_and_no_ragged_dot(compiled)


def test_the_index_searches_and_applies_this_cell_s_rows(one_chip, no_persistent_cache):
    """65,536 slots of 2,048 float32 values beside 10.59 GB of weights: the
    megakernel at the query batches the cell sends, and the undonated
    scatter of a flush's rows (the matrix in, the matrix out)."""
    from pathway_tpu.ops import fused_serving as fs
    from pathway_tpu.ops import knn

    config, _cfg, _params = _latent_model(one_chip)
    n, d = config["index"]["capacity"], config["index"]["dim"]
    assert (n, d) == (65_536, 2048)
    shape = lambda s, t: jax.ShapeDtypeStruct(s, t, sharding=one_chip)
    block = fs.validate_serving_geometry(n, "cos")
    fn = getattr(fs._pallas_fused_dense, "__wrapped__", fs._pallas_fused_dense)
    for q_b, q_dtype in ((8, jnp.bfloat16), (32, jnp.float32)):
        compiled = fn.lower(shape((q_b, d), q_dtype), shape((n, d), jnp.float32),
                            shape((n,), jnp.bool_), k=16, q_b=q_b, metric="cos",
                            normalize=True, qdt="f32", block_n=block, interpret=False).compile()
        assert "tpu_custom_call" in compiled.as_text()
    scatter = getattr(knn._scatter_rows_dropping, "__wrapped__", knn._scatter_rows_dropping)
    compiled = scatter.lower(shape((n, d), jnp.float32), shape((32,), jnp.int32),
                             shape((32, d), jnp.float32), normalize=True).compile()
    memory = compiled.memory_analysis()
    assert memory.argument_size_in_bytes + memory.output_size_in_bytes < 2 * 0.54e9
    mask = getattr(knn._scatter_mask, "__wrapped__", knn._scatter_mask)
    mask.lower(shape((n,), jnp.bool_), shape((32,), jnp.int32),
               shape((32,), jnp.bool_)).compile()


# -- the conv embedder's cell ---------------------------------------------------

def test_the_conv_packed_forward_compiles_at_each_token_bucket_beside_the_index(
        one_chip, no_persistent_cache, monkeypatch):
    """10.53 GB of weights (64 experts of 1,536 in eight layers, the whole
    vocabulary).  Each launch that serves: its temporaries (four routed rows
    a token through experts of 1,536, ``W_in``'s 6,144 columns a token on
    eight layers) have to fit beside the weights and the three copies of the
    index's 0.54 GB that an apply holds."""
    import numpy as np

    from pathway_tpu.models import causal_moe_embedder as cme
    from pathway_tpu.models.encoder import dispatch_dtype, ragged_chunk

    _the_chip_s_grouped_product(monkeypatch)
    _config, cfg, params = _cell_model(one_chip, "lfm2", "vs-lfm2-24b-a2b-bf16-marcodoc", cme)
    assert set(cfg.layer_types) == {"conv", "full"} and len(cfg.token_buckets) == 4
    model = cme.CausalMoeEmbedder(cfg, packed=True)
    none = np.zeros(0, np.int64)
    for tokens in cfg.token_buckets:
        chunk = ragged_chunk(none, none, None, None, cfg.max_len,
                             dispatch_dtype(cfg.vocab_size), cfg, tokens=tokens)
        args = [jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip)
                for a in (chunk.ids, chunk.pos, chunk.seg, chunk.starts)]
        compiled = jax.jit(lambda p, *a: model.apply({"params": p}, *a)).lower(
            params, *args).compile()
        memory = compiled.memory_analysis()
        assert memory.argument_size_in_bytes == pytest.approx(10.534e9, rel=0.01)  # bfloat16
        assert (memory.argument_size_in_bytes + memory.temp_size_in_bytes
                + 3 * 0.537e9 < 15.75e9), tokens
        _assert_the_kernel_and_no_ragged_dot(compiled)


# -- the delta-rule embedder's cell ---------------------------------------------

@pytest.mark.parametrize("tokens", [1536, 6144])
def test_the_delta_rule_scan_kernel_compiles_at_the_published_shapes(
        one_chip, no_persistent_cache, tokens):
    """32 heads of 128 key and value channels, chunks of 64 in sub-blocks of
    16, the heads' states in VMEM: interpret mode knows nothing of Mosaic's
    tiles or of VMEM."""
    from pathway_tpu.ops import kda_scan as K

    h, d = 32, 128
    shape = lambda s, t=jnp.float32: jax.ShapeDtypeStruct(s, t, sharding=one_chip)
    args = [shape((tokens, h, d)) for _ in range(4)] + [
        shape((tokens, h)), shape((tokens,), jnp.int32), shape((tokens,), jnp.int32),
        shape((tokens,), jnp.bool_)]
    compiled = jax.jit(K.kda_scan_pallas).lower(*args).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text and K.KERNEL_NAME in text


def _kimi_model(one_chip):
    from pathway_tpu.models import causal_moe_embedder as cme

    return _cell_model(one_chip, "kimi_linear", "vs-kimi-linear-48b-a3b-bf16-marcodoc", cme)


def test_the_delta_rule_packed_forward_compiles_at_each_token_bucket_beside_the_index(
        one_chip, no_persistent_cache, monkeypatch):
    """8.57 GB of weights (128 of 256 experts of 1,024 in four layers, the
    whole vocabulary of 163,840), with the scan's and the grouped product's
    kernels in it.  Each launch that serves: its temporaries (``W_qkv``'s
    12,288 columns a token in float32 on four layers, eight routed rows a
    token) have to fit beside the weights and the three copies of the
    index's 0.60 GB that an apply holds."""
    import numpy as np

    from pathway_tpu.models import causal_moe_embedder as cme
    from pathway_tpu.models.encoder import dispatch_dtype, ragged_chunk
    from pathway_tpu.ops import kda_scan as K

    _the_chip_s_grouped_product(monkeypatch)
    monkeypatch.setattr(cme, "kda_scan", K.kda_scan_pallas)
    _config, cfg, params = _kimi_model(one_chip)
    assert cfg.layer_types == ("kda", "kda", "kda", "latent", "kda")
    assert (cfg.num_experts, cfg.experts_here, cfg.first_expert) == (256, 128, 0)
    model = cme.CausalMoeEmbedder(cfg, packed=True)
    none = np.zeros(0, np.int64)
    for tokens in cfg.token_buckets:
        chunk = ragged_chunk(none, none, None, None, cfg.max_len,
                             dispatch_dtype(cfg.vocab_size), cfg, tokens=tokens)
        args = [jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip)
                for a in (chunk.ids, chunk.pos, chunk.seg, chunk.starts)]
        compiled = jax.jit(lambda p, *a: model.apply({"params": p}, *a)).lower(
            params, *args).compile()
        memory = compiled.memory_analysis()
        assert memory.argument_size_in_bytes == pytest.approx(8.566e9, rel=0.01)  # bfloat16
        assert (memory.argument_size_in_bytes + memory.temp_size_in_bytes
                + 3 * 0.604e9 < 15.75e9), tokens
        _assert_the_kernel_and_no_ragged_dot(compiled)
        assert K.KERNEL_NAME in compiled.as_text()


def test_the_delta_rule_check_s_layer_compiles_at_the_published_widths(
        one_chip, no_persistent_cache, monkeypatch):
    """What ``layer_gap`` calls in the cell: one KDA block over the longest
    document (2,048 tokens), with the scan's kernel in it."""
    from pathway_tpu.models import causal_moe_embedder as cme
    from pathway_tpu.ops import kda_scan as K

    _the_chip_s_grouped_product(monkeypatch)
    monkeypatch.setattr(cme, "kda_scan", K.kda_scan_pallas)
    config, cfg, params = _kimi_model(one_chip)  # puts perfbench/ on the path
    from encoders import kimi_linear as builder

    x = jax.ShapeDtypeStruct((cfg.max_len, cfg.hidden_dim), jnp.float32, sharding=one_chip)
    program = builder._layer_program(json.dumps(config, sort_keys=True), 1)
    compiled = program.lower(params["layer_1"], x).compile()
    assert K.KERNEL_NAME in compiled.as_text()
    assert compiled.memory_analysis().temp_size_in_bytes < 2e9


#: sha256 of each sibling cell's packed program at its first token bucket,
#: lowered for the described chip with the kernels' serialised bodies taken
#: out (they carry the source's path): the delta rule, the direct query,
#: the no-rotary form and the expert share lower to nothing in these programs
_SIBLING_PROGRAMS = {
    "laguna": ("vs-laguna-xs2-bf16-marcodoc",
               "0f0dd1cbd5063fbe963dd11b9f3b7b21a2d1641731519375ce047ff2d1246522"),
    "joyai": ("vs-joyai-flash-bf16-marcodoc",
              "836ff5a33089b0c29bcdac469a900f67b20aa54563b4118b8686ca031e6460ac"),
    "lfm2": ("vs-lfm2-24b-a2b-bf16-marcodoc",
             "3362e4640ae88d189406b602f0fc6d5b940f9953950273199a0fab344e565cfe"),
    "falcon_h1": ("vs-falcon-h1-34b-bf16-marcodoc",
                  "d4095770bd59c5bb9e7480c074d864093d9f934c6fa3d6233f60b878b1603cee"),
}


@pytest.mark.parametrize("builder_name", sorted(_SIBLING_PROGRAMS))
def test_the_sibling_cells_packed_programs_lower_as_before(
        one_chip, no_persistent_cache, monkeypatch, builder_name):
    """The packed programs of the four other language-model cells lower to
    the same text as before the delta-rule layer kind, the latent kind's
    direct query and no-rotary form and the routed layer's expert share were
    added: a change here that reaches them changes their cells."""
    import hashlib
    import re

    import numpy as np

    from pathway_tpu.models import causal_hybrid_embedder as che
    from pathway_tpu.models import causal_moe_embedder as cme
    from pathway_tpu.models.encoder import dispatch_dtype, ragged_chunk
    from pathway_tpu.ops import ssd_scan as S

    _the_chip_s_grouped_product(monkeypatch)
    monkeypatch.setattr(che, "ssd_scan", S.ssd_scan_pallas)
    config_name, want = _SIBLING_PROGRAMS[builder_name]
    hybrid = builder_name == "falcon_h1"
    _config, cfg, params = _cell_model(one_chip, builder_name, config_name,
                                       che if hybrid else cme)
    model = (che.CausalHybridEmbedder if hybrid else cme.CausalMoeEmbedder)(cfg, packed=True)
    none = np.zeros(0, np.int64)
    chunk = ragged_chunk(none, none, None, None, cfg.max_len, dispatch_dtype(cfg.vocab_size),
                         cfg, tokens=cfg.token_buckets[0])
    args = [jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip)
            for a in (chunk.ids, chunk.pos, chunk.seg, chunk.starts)]
    text = jax.jit(lambda p, *a: model.apply({"params": p}, *a)).lower(params, *args).as_text()
    text = re.sub(r'backend_config = "[^"]*"', 'backend_config = ""', text)
    assert hashlib.sha256(text.encode()).hexdigest() == want
