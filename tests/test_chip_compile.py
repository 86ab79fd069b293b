"""The main path's kernels, compiled at real widths for a described v5e.

The TPU's compiler is installed here and compiles for a chip that is
described, not attached — so what it would refuse on the chip (a tile it
cannot lower, more VMEM than a kernel may have) fails in tier-1 at no chip
time. Interpret-mode tests cannot see any of that. Nothing runs: these
say nothing about results or speed, and a compile that passes is not a
chip run.

The topology is described inside the module-scoped ``topo`` fixture —
never at import or collection time, and only in this one file: the first
process to load the TPU library keeps it until it exits, so every xdist
worker must be able to COLLECT this file without touching it.
"""

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from commefficient_tpu.ops import sketch_kernels, topk_kernels
from commefficient_tpu.ops.countsketch import CountSketch

D_RESNET9 = 6_568_640          # ResNet-9, the paper's flagship
D_GPT2 = 124_440_576           # GPT2-small double-heads
D_NEMOTRON_CUT = 666_962_944   # benchmarks/configs/nemotron3-nano-30b-a3b.json
ROWS, COLS, K = 5, 500_000, 50_000   # reference sketch (utils.py:142-145)


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:   # no TPU compiler, or its library is taken
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # an executable compiled for a described chip is written to the
    # persistent cache but cannot be read back without the chip: keep
    # these compiles out of it
    was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was_on)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compiled(fn, one_chip, *shapes_dtypes):
    args = [jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
            for shape, dtype in shapes_dtypes]
    return jax.jit(fn).lower(*args).compile()


def _compiled_text(fn, one_chip, *shapes_dtypes):
    return _compiled(fn, one_chip, *shapes_dtypes).as_text()


def _sketch(d):
    return CountSketch(d=d, c=COLS, r=ROWS)


def _table(cs):
    return ((cs.r, cs.c_eff), jnp.float32)


def test_sketch_vec_resnet9(one_chip):
    cs = _sketch(D_RESNET9)
    text = _compiled_text(lambda v: sketch_kernels.sketch_vec_pallas(cs, v),
                          one_chip, ((cs.d,), jnp.float32))
    assert "tpu_custom_call" in text


def test_estimates_resnet9(one_chip):
    cs = _sketch(D_RESNET9)
    text = _compiled_text(lambda t: sketch_kernels.estimates_pallas(cs, t),
                          one_chip, _table(cs))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("r", [1, 3])
@pytest.mark.parametrize("kernel", ["sketch_vec", "estimates"])
def test_hash_kernels_at_narrower_medians(one_chip, kernel, r):
    """The r = 1 and r = 3 bodies beside the r = 5 ones above: r lane
    gathers a tile and a narrower median network."""
    cs = CountSketch(d=D_RESNET9, c=COLS, r=r)
    if kernel == "sketch_vec":
        text = _compiled_text(
            lambda v: sketch_kernels.sketch_vec_pallas(cs, v), one_chip,
            ((cs.d,), jnp.float32))
    else:
        text = _compiled_text(
            lambda t: sketch_kernels.estimates_pallas(cs, t), one_chip,
            _table(cs))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("d", [D_RESNET9, D_GPT2, D_NEMOTRON_CUT],
                         ids=["resnet9", "gpt2_small", "nemotron_cut"])
def test_unsketch_select(one_chip, d):
    from commefficient_tpu.utils import tracing
    cs = _sketch(d)

    def in_the_servers_phase(t):
        with tracing.phase("server_update"):
            return topk_kernels.unsketch_select_pallas(cs, t, k=K)

    compiled = _compiled(in_the_servers_phase, one_chip, _table(cs))
    text = compiled.as_text()
    # one estimates pass, the loop's count, the final count, the select:
    # under these names a device trace is read, in this phase
    for name in ("estimates_pallas", "radix_count_pallas",
                 "unsketch_select_pallas"):
        assert f"%{name}" in text, name
    assert text.count('custom_call_target="tpu_custom_call"') == 4
    # the count walks its own blocks of COUNT_ROWS rows, and at all three
    # d (81 417 tiles of 64 rows at the hybrid's cut) the last one
    # overhangs the buffer
    rows = -(-cs.d // topk_kernels.TILE_N) * topk_kernels.TILE_BLOCKS
    assert rows % topk_kernels.COUNT_ROWS != 0
    assert {phase for key, phase in tracing.op_phases(compiled).items()
            if key.startswith("%estimates_pallas")} == {"server_update"}


def test_three_hash_passes_share_one_array_of_window_bases(one_chip):
    """Sketch, estimate, re-sketch in one program, as a sketch round runs
    them: each custom call takes the window bases as its second operand,
    and the three equal ``window_bases`` expressions compile to ONE array
    (XLA's CSE) that all three calls read: hashed once a round."""
    import re
    cs = _sketch(D_RESNET9)

    def three_passes(v):
        table = cs.sketch_vec_batched(v, use_kernel=True)
        update = topk_kernels.unsketch_select_pallas(cs, table, k=K)
        return cs.sketch_vec_batched(update, use_kernel=True)

    # the dispatch gate asks the backend, which is the CPU here
    with sketch_kernels.force_dispatch("kernel"), \
            pytest.MonkeyPatch.context() as patch:
        patch.setattr(jax, "default_backend", lambda: "tpu")
        text = _compiled_text(three_passes, one_chip,
                              ((cs.d,), jnp.float32))
    calls = re.findall(
        r"%((?:sketch_vec|estimates)_pallas[.\d]*) = [^\n]*? "
        r"custom-call\(([^)]*)\), custom_call_target=\"tpu_custom_call\"",
        text)
    assert sorted(name.split(".")[0] for name, _ in calls) == [
        "estimates_pallas", "sketch_vec_pallas", "sketch_vec_pallas"]
    operands = [[o.split()[-1] for o in ops.split(", ")] for _, ops in calls]
    assert all(len(ops) == 2 for ops in operands), operands
    assert len({ops[1] for ops in operands}) == 1, operands


@pytest.mark.parametrize("d", [D_RESNET9, D_GPT2],
                         ids=["resnet9", "gpt2_small"])
def test_fused_true_topk(one_chip, d):
    vec = ((d,), jnp.float32)
    text = _compiled_text(
        lambda g, v, e: topk_kernels.fused_true_topk_pallas(
            g, v, e, k=K, rho=0.9),
        one_chip, vec, vec, vec)
    assert "tpu_custom_call" in text


def test_flash_attention_fwd_bwd_dropout(one_chip):
    from commefficient_tpu.ops.flash_attention import flash_attention
    key = jax.random.PRNGKey(0)

    def loss(q, k, v):
        return jnp.sum(flash_attention(
            q, k, v, dropout_rate=0.1, dropout_key=key
        ).astype(jnp.float32) ** 2)

    qkv = ((8, 256, 12, 64), jnp.bfloat16)
    text = _compiled_text(jax.grad(loss, argnums=(0, 1, 2)), one_chip,
                          qkv, qkv, qkv)
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("kernel", ["sketch_vec", "unsketch_select"])
def test_sketch_kernels_at_the_hybrid_models_cut(one_chip, kernel):
    """The same two kernels at a d 101 times ResNet-9's (the cell
    nemotron3-nano-30b-a3b.sketch): the grid grows with d, the table does
    not."""
    cs = _sketch(D_NEMOTRON_CUT)
    if kernel == "sketch_vec":
        text = _compiled_text(
            lambda v: sketch_kernels.sketch_vec_pallas(cs, v), one_chip,
            ((cs.d,), jnp.float32))
    else:
        compiled = _compiled(
            lambda t: topk_kernels.unsketch_select_pallas(cs, t, k=K),
            one_chip, _table(cs))
        text = compiled.as_text()
        # the call's temporaries: the estimates in the tiled layout, once
        # (the select pass writes over them; the output is their slice to
        # d). The parent of PR 36 held two d-long outputs of its select
        # pass here: 5 335 809 024 bytes.
        temp = compiled.memory_analysis().temp_size_in_bytes
        one_buffer = -(-cs.d // topk_kernels.TILE_N) * topk_kernels.TILE_N * 4
        assert temp <= 5_335_809_024
        assert one_buffer <= temp < one_buffer + (1 << 20)
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("kind", ["M", "E", "*"])
def test_hybrid_model_layer_fwd_bwd_at_published_widths(one_chip,
                                                        monkeypatch, kind):
    """One layer of each kind of models/nemotron_h.py at the published
    widths and the cell's 8 x 2048 tokens, bfloat16 operands, forward and
    backward through its rematerialisation: the grouped expert product
    (``lax.ragged_dot``: a Mosaic kernel on the chip), the chunked scan,
    grouped-query flash attention."""
    import dataclasses

    from commefficient_tpu.models.nemotron_h import Block, NemotronHConfig
    # attention asks the backend whether its kernel can run; this compile is
    # for the chip, so steer it here (never through an option of the program)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cfg = dataclasses.replace(NemotronHConfig(), pattern=kind,
                              experts_held=tuple(range(8)),
                              compute_dtype="bfloat16")
    block = Block(cfg, kind)
    x = jax.ShapeDtypeStruct((8, 2048, cfg.hidden_size), jnp.float32,
                             sharding=one_chip)
    params = jax.eval_shape(block.init, jax.random.PRNGKey(0), x)["params"]
    params = jax.tree.map(lambda s: jax.ShapeDtypeStruct(
        s.shape, s.dtype, sharding=one_chip), params)

    def loss(p, x):
        return jnp.sum(jax.checkpoint(
            lambda p, x: block.apply({"params": p}, x))(p, x) ** 2)

    compiled = jax.jit(jax.grad(loss)).lower(params, x).compile()
    assert ("tpu_custom_call" in compiled.as_text()) == (kind != "M")
    assert compiled.memory_analysis().temp_size_in_bytes < 6e9


@pytest.mark.parametrize("op", ["sketch_vec", "estimates"])
def test_batched_per_worker(one_chip, monkeypatch, op):
    """What federated/client.py and client_store.py dispatch under the
    round's per-worker vmap — whenever a per-worker nonlinearity
    (--max_grad_norm, DP, local error) rules out sketch-after-aggregate,
    and for the sketched client-state codec: the 2-D grid (W, n_tiles)
    kernels, one table block per worker. At W=8 the default 16 MiB of
    scoped VMEM refused both (the per-row table block is double-buffered);
    ``sketch_kernels._batched_params`` raises the limit."""
    cs = _sketch(D_RESNET9)
    W = 8
    fn, shape = {
        "sketch_vec": (lambda v: cs.sketch_vec(v, True), (W, cs.d)),
        "estimates": (lambda t: cs.estimates(t, True), (W,) + _table(cs)[0]),
    }[op]
    # the dispatch override interprets off-TPU; this compile is for the
    # chip, so steer it here (never through an option of the program)
    monkeypatch.setattr(sketch_kernels, "_interpret", lambda flag: False)
    with sketch_kernels.force_dispatch("kernel"):
        text = _compiled_text(jax.vmap(fn), one_chip, (shape, jnp.float32))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("op", ["sketch_vec", "estimates"])
def test_batched_grid_at_the_hybrid_models_cut(one_chip, monkeypatch, op):
    """The 2-D grid (batch, n_tiles) kernels at the hybrid's d, as its round
    runs them: the server's and the aggregate's calls are a singleton vmap
    (``sketch_vec_batched`` / ``estimates_batched``), 81 417 tiles a row."""
    cs = _sketch(D_NEMOTRON_CUT)
    fn, shape = {
        "sketch_vec": (lambda v: cs.sketch_vec_batched(v, True), (cs.d,)),
        "estimates": (lambda t: cs.estimates_batched(t, True),
                      _table(cs)[0]),
    }[op]
    monkeypatch.setattr(sketch_kernels, "_interpret", lambda flag: False)
    with sketch_kernels.force_dispatch("kernel"):
        text = _compiled_text(fn, one_chip, (shape, jnp.float32))
    assert "tpu_custom_call" in text
