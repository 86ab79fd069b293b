"""The hybrid Mamba-2 / sparse-expert / attention model against its plain
reference (``benchmarks/reference/nemotron_h_fetchsgd.py``), at the tiny
size: hidden 64, 2 scan heads, 8 experts of which 2 held, pattern ``EM*``,
vocabulary 256, seeded weights made by the reference."""

import dataclasses
import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.flatten_util import ravel_pytree

from commefficient_tpu.models.nemotron_h import (NemotronH, NemotronHConfig,
                                                 logits)
from commefficient_tpu.ops.moe import MoEFFN
from commefficient_tpu.ops.ssd import ssd_chunked

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmarks")
T = 21          # not a multiple of the tiny chunk (8)


def _load(kind, name):
    spec = importlib.util.spec_from_file_location(
        f"tiny_{name}", os.path.join(BENCH, kind, name + ".py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def tiny_model_group(cfg, seq_len=T):
    group = {k: getattr(cfg, k) for k in (
        "pattern", "hidden_size", "vocab_rows", "norm_eps",
        "mamba_num_heads", "mamba_head_dim", "n_groups", "ssm_state_size",
        "conv_kernel", "time_step_min", "time_step_max", "time_step_floor",
        "n_routed_experts", "num_experts_per_tok", "moe_intermediate_size",
        "moe_shared_expert_intermediate_size", "routed_scaling_factor",
        "norm_topk_prob", "num_attention_heads", "num_key_value_heads",
        "head_dim")}
    group["experts_held"] = list(cfg.experts_held)
    group["seq_len"] = seq_len
    return group


@pytest.fixture(scope="module")
def ref():
    reference = _load("reference", "nemotron_h_fetchsgd")
    reference.configure(tiny_model_group(NemotronHConfig.tiny()))
    return reference


@pytest.fixture(scope="module")
def tiny(ref):
    """(config, model, flat weights of seed 7, unflatten, ids, labels)."""
    cfg = NemotronHConfig.tiny()
    model = NemotronH(cfg)
    ids = jax.random.randint(jax.random.PRNGKey(1), (3, T), 0, 256)
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0), ids)["params"]
    flat0, unflatten = ravel_pytree(
        jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype), shapes))
    flat = ref.make_weights(7)
    assert flat.shape == flat0.shape
    names = ["/".join(k.key for k in path) for path, _ in
             jax.tree_util.tree_flatten_with_path(shapes)[0]]
    assert names == [name for name, _ in ref.LAYOUT]
    labels = jnp.concatenate([ids[:, 1:], jnp.full((3, 1), -1)], axis=1)
    return cfg, model, flat, unflatten, ids, labels


def test_logits_and_loss_match_the_reference(ref, tiny):
    from commefficient_tpu.federated.losses import make_lm_loss
    cfg, model, flat, unflatten, ids, labels = tiny
    with jax.default_matmul_precision("highest"):
        params = unflatten(flat)
        got = logits(params, model.apply({"params": params}, ids))
        want = ref.logits_fn(ref.unflatten(flat), ids, "float32")
        np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-5)
        loss, rows = make_lm_loss(model, train=True)(
            params, (ids, labels), None, True)
        np.testing.assert_allclose(
            loss, ref.sequence_losses(flat, ids, labels, "float32"),
            rtol=1e-5)
    # 63 tokens x top-6 of 8 experts, 2 held: what landed here, nothing lost
    assert rows.shape == (3, 3)
    assert 0 < float(rows[0].sum()) <= 63 * 2
    assert float(rows[1].sum()) <= float(rows[0].sum())
    assert float(rows[2].sum()) == 0.0


def test_gradients_match_the_reference_leaf_by_leaf(ref, tiny):
    from commefficient_tpu.federated.losses import make_lm_loss
    cfg, model, flat, unflatten, ids, labels = tiny
    loss_fn = make_lm_loss(model, train=True)
    mask = jnp.array([1.0, 1.0, 0.0])

    def program(f):
        return jnp.sum(loss_fn(unflatten(f), (ids, labels), None, True)[0]
                       * mask)

    with jax.default_matmul_precision("highest"):
        got = jax.jit(jax.grad(program))(flat)
        _, want = ref._block_grad(flat, ids, labels, mask, "float32", None)
    for name, a, b in ref.leaf_slices():
        scale = float(jnp.max(jnp.abs(want[a:b]))) + 1e-12
        np.testing.assert_allclose(got[a:b] / scale, want[a:b] / scale,
                                   atol=2e-4, err_msg=name)


@pytest.mark.parametrize("steps", [T, 16, 5])
def test_chunked_scan_matches_the_recurrence(ref, steps):
    b, H, P, G, N = 2, 4, 8, 2, 16
    k = jax.random.split(jax.random.PRNGKey(steps), 5)
    x = jax.random.normal(k[0], (b, steps, H, P))
    dt = jax.nn.softplus(jax.random.normal(k[1], (b, steps, H)))
    A = -jnp.exp(jax.random.normal(k[2], (H,)))
    B = jax.random.normal(k[3], (b, steps, G, N))
    C = jax.random.normal(k[4], (b, steps, G, N))
    D = jnp.linspace(0.5, 1.5, H)

    def both(fn):
        return jax.value_and_grad(
            lambda *a: jnp.sum(jnp.sin(fn(*a))), argnums=(0, 1, 2, 3, 4))(
                x, dt, A, B, C)

    with jax.default_matmul_precision("highest"):
        y0, g0 = both(lambda *a: ref.selective_scan(*a, D))
        y1, g1 = both(lambda *a: ssd_chunked(*a, D, chunk=8))
    np.testing.assert_allclose(y1, y0, rtol=1e-5)
    for a, b_ in zip(g1, g0):
        np.testing.assert_allclose(a, b_, rtol=1e-4,
                                   atol=1e-5 * float(jnp.max(jnp.abs(b_))))


def _expert_layer(cfg, held, **kw):
    return MoEFFN(cfg.n_routed_experts, cfg.moe_intermediate_size,
                  experts_held=held, top_k=cfg.num_experts_per_tok,
                  scoring="sigmoid", norm_topk=True,
                  routed_scale=cfg.routed_scaling_factor,
                  activation="relu2", use_bias=False, aux_loss=False, **kw)


def _layer_params(ref, tiny, held):
    """The first (E) layer's parameters of the seeded weights, with the
    experts ``held`` drawn from one (8, ...) stack of the seed."""
    cfg, model, flat, unflatten, _, _ = tiny
    p = dict(unflatten(flat)["layers_00"]["mixer"])
    key = jax.random.PRNGKey(11)
    C, F = cfg.hidden_size, cfg.moe_intermediate_size
    w1 = jax.random.normal(key, (8, C, F)) * 0.05
    w2 = jax.random.normal(jax.random.fold_in(key, 1), (8, F, C)) * 0.05
    p["moe_w1"], p["moe_w2"] = w1[jnp.asarray(held)], w2[jnp.asarray(held)]
    flat_names = {"layers_00/mixer/" + k: v for k, v in (
        ("moe_w1", p["moe_w1"]), ("moe_w2", p["moe_w2"]),
        ("router/kernel", p["router"]["kernel"]),
        ("shared_up/kernel", p["shared_up"]["kernel"]),
        ("shared_down/kernel", p["shared_down"]["kernel"]))}
    return p, flat_names


def test_the_shares_add_up_to_the_uncut_layer(ref, tiny):
    """The routed parts of all 4 shares of 2 experts, plus the shared
    expert counted once, equal the uncut layer (all 8 experts held)."""
    cfg = tiny[0]
    u = jax.random.normal(jax.random.PRNGKey(5), (40, cfg.hidden_size))
    shared_width = cfg.moe_shared_expert_intermediate_size
    with jax.default_matmul_precision("highest"):
        total = 0.0
        for share in range(4):
            held = (2 * share, 2 * share + 1)
            p, names = _layer_params(ref, tiny, held)
            routed = {k: v for k, v in p.items()
                      if not k.startswith("shared")}
            part = _expert_layer(cfg, held).apply({"params": routed}, u)
            np.testing.assert_allclose(
                part, ref._experts(names, "layers_00/mixer/", u, "float32",
                                   held=held, shared=False),
                rtol=1e-4, atol=1e-6)
            total = total + part
        p, names = _layer_params(ref, tiny, tuple(range(8)))
        whole = _expert_layer(cfg, None, shared_d_ff=shared_width).apply(
            {"params": p}, u)
        shared = whole - _expert_layer(cfg, None).apply(
            {"params": {k: v for k, v in p.items()
                        if not k.startswith("shared")}}, u)
        np.testing.assert_allclose(total + shared, whole, rtol=1e-4,
                                   atol=1e-6)
        np.testing.assert_allclose(
            whole, ref._experts(names, "layers_00/mixer/", u, "float32",
                                held=list(range(8))), rtol=1e-4, atol=1e-6)


def test_ties_and_the_bias_choose_as_the_reference_does(ref, tiny):
    """A router of zeros scores every expert 0.5: top-6 of equal values is
    experts 0..5; a bias moves the choice and not the weights."""
    cfg = tiny[0]
    held = (0, 1, 6, 7)
    p, names = _layer_params(ref, tiny, held)
    p["router"] = {"kernel": jnp.zeros_like(p["router"]["kernel"])}
    names["layers_00/mixer/router/kernel"] = p["router"]["kernel"]
    u = jax.random.normal(jax.random.PRNGKey(6), (24, cfg.hidden_size))
    layer = _expert_layer(
        cfg, held, shared_d_ff=cfg.moe_shared_expert_intermediate_size)
    with jax.default_matmul_precision("highest"):
        for bias in (jnp.zeros((8,)),
                     jnp.array([0., -1., 0., 0., 0., 0., 1., 0.]),
                     jnp.array([0., 0., 0., 0., 0., 0., 0., 1e-3])):
            got, inter = layer.apply(
                {"params": p, "buffers": {"score_bias": bias}}, u,
                mutable=["intermediates"])
            want = ref._experts(names, "layers_00/mixer/", u, "float32",
                                score_bias=bias, held=held)
            np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-6)
            chosen = set(np.argsort(-np.asarray(0.5 + bias),
                                    kind="stable")[:6].tolist())
            assert (float(inter["intermediates"]["moe_held"][0].sum())
                    == 24 * len(chosen & set(held)))


def test_nothing_is_dropped_when_every_token_takes_one_expert(ref, tiny):
    """A bias that sends every token to experts 0..5, of which this chip
    holds 0 and 1: twice the rows an even routing gives, walked in blocks of
    16 so that later blocks run (and the last ones are skipped)."""
    cfg = tiny[0]
    held = (0, 1)
    p, names = _layer_params(ref, tiny, held)
    bias = jnp.array([9., 9., 9., 9., 9., 9., 0., 0.])
    u = jax.random.normal(jax.random.PRNGKey(8), (40, cfg.hidden_size))
    layer = _expert_layer(
        cfg, held, rows_per_block=16,
        shared_d_ff=cfg.moe_shared_expert_intermediate_size)
    with jax.default_matmul_precision("highest"):
        got, inter = jax.jit(lambda p: layer.apply(
            {"params": p, "buffers": {"score_bias": bias}}, u,
            mutable=["intermediates"]))(p)
        want = ref._experts(names, "layers_00/mixer/", u, "float32",
                            score_bias=bias, held=held)
        grad = jax.grad(lambda p: jnp.sum(layer.apply(
            {"params": p, "buffers": {"score_bias": bias}}, u) ** 2))(p)
    inter = inter["intermediates"]
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-6)
    assert float(inter["moe_held"][0].sum()) == 80
    assert float(inter["moe_fullest"][0].sum()) == 40
    assert float(inter["moe_dropped"][0].sum()) == 0.0
    assert all(bool(jnp.all(jnp.isfinite(g)))
               for g in jax.tree_util.tree_leaves(grad))


def test_rows_past_the_last_assignment_leak_nothing(ref, tiny, monkeypatch):
    """On the chip the grouped product leaves whatever the memory held in
    the rows that belong to no expert, in its result and in the cotangent
    of its rows (PR 35: the embedding's gradient read 1e4 for 2). Here a
    grouped product that poisons those rows, both ways: the layer's result
    and its gradients are what they are without the poison."""
    cfg = tiny[0]
    held = (0, 1)
    p, _ = _layer_params(ref, tiny, held)
    u = jax.random.normal(jax.random.PRNGKey(9), (40, cfg.hidden_size))
    layer = _expert_layer(cfg, held, rows_per_block=64)
    plain = jax.lax.ragged_dot

    def past(group_sizes, rows):
        return (jnp.arange(rows) >= jnp.sum(group_sizes))[:, None]

    @jax.custom_vjp
    def poisoned(lhs, rhs, group_sizes):
        out = plain(lhs, rhs, group_sizes)
        return jnp.where(past(group_sizes, lhs.shape[0]), 1e30, out)

    def fwd(lhs, rhs, group_sizes):
        return poisoned(lhs, rhs, group_sizes), (lhs, rhs, group_sizes)

    def bwd(res, g):
        lhs, rhs, group_sizes = res
        dead = past(group_sizes, lhs.shape[0])
        _, vjp = jax.vjp(lambda a, b: plain(a, b, group_sizes), lhs, rhs)
        d_lhs, d_rhs = vjp(jnp.where(dead, 0.0, g))
        return jnp.where(dead, 1e30, d_lhs), d_rhs, None

    poisoned.defvjp(fwd, bwd)

    def both():
        def total(p, u):
            return jnp.sum(layer.apply({"params": p}, u) ** 2)
        return jax.value_and_grad(total, argnums=(0, 1))(p, u)

    with jax.default_matmul_precision("highest"):
        want = both()
        monkeypatch.setattr(
            jax.lax, "ragged_dot",
            lambda a, b, sz, preferred_element_type=None: poisoned(a, b, sz))
        got = both()
    for a, b in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-7)


def test_one_sketch_round_through_train_equals_the_reference(ref, tiny,
                                                            tmp_path):
    """``training.gpt2.train`` (the same FedLearner, round and server as
    GPT2) for two rounds against ``reference.steps`` on the same batches."""
    from commefficient_tpu.federated.api import FedLearner
    from commefficient_tpu.training import gpt2
    from commefficient_tpu.utils import tracing
    _load("datagen", "token_docs").write(
        str(tmp_path), seed=3, num_clients=8, tokens_per_client=4 * 32,
        valid_tokens=4 * 32, vocab_rows=256)
    ref.configure(tiny_model_group(NemotronHConfig.tiny(), seq_len=32))
    argv = ["--model", "nemotron_h-tiny", "--dataset_name", "TOKENS",
            "--dataset_dir", str(tmp_path), "--max_seq_len", "32",
            "--mode", "sketch", "--error_type", "virtual",
            "--virtual_momentum", "0.9", "--k", "200", "--num_rows", "5",
            "--num_cols", "4000", "--num_clients", "8", "--num_workers",
            "4", "--local_batch_size", "1", "--weight_decay", "0",
            "--lr_scale", "0.05", "--num_epochs", "2", "--seed", "5",
            "--valid_batch_size", "4"]
    args = gpt2.build_gpt2_parser().parse_args(argv)
    seen, dispatch = [], FedLearner.train_round_async
    w0 = np.asarray(ref.make_weights(5))

    def recording(self, ids, cols, mask, **kw):
        if not seen:
            self.state = self.state.replace(weights=jnp.asarray(w0))
        seen.append(jax.device_get((cols, mask)))
        return dispatch(self, ids, cols, mask, **kw)

    tracing.reset()
    FedLearner.train_round_async = recording
    try:
        with jax.default_matmul_precision("highest"):
            learner, row = gpt2.train(args, max_rounds=2, log=False)
    finally:
        FedLearner.train_round_async = dispatch
    assert len(seen) == 2 and learner.cfg.grad_size == ref.D
    spec = {"mode": "sketch", "k": 200, "num_rows": 5, "num_cols": 4000,
            "virtual_momentum": 0.9, "weight_decay": 0.0, "num_workers": 4,
            "lr_scale": 0.05,
            "total_steps": int(learner.lr_schedule.knots[1])}
    batches = [(c[0].reshape(-1, 32), c[1].reshape(-1), m.reshape(-1))
               for c, m in seen]
    with jax.default_matmul_precision("highest"):
        want = ref.steps(w0, batches, spec, "float32")
    np.testing.assert_allclose(np.asarray(learner.state.weights),
                               want["w"], rtol=1e-4, atol=1e-7)
    assert abs(row["train_loss"] - np.mean(want["loss"])) < 1e-4
    counts = tracing.snapshot()["counters"]
    assert counts["moe.assignments_held"][0] > 0
    assert counts["moe.dropped"][0] == 0
    ref.configure(tiny_model_group(NemotronHConfig.tiny()))


def test_the_cut_keys_and_nothing_else_change_the_parameter_count():
    """The benchmark's cut (9 layers EMEMEMEM*, 8 experts held, 16 384 rows)
    at published widths, counted from shapes alone."""
    cfg = dataclasses.replace(NemotronHConfig(), pattern="EMEMEMEM*",
                              experts_held=tuple(range(8)), vocab_rows=16384)
    shapes = jax.eval_shape(NemotronH(cfg).init, jax.random.PRNGKey(0),
                            jnp.zeros((1, 128), jnp.int32))["params"]
    d = sum(int(np.prod(s.shape)) for s in jax.tree_util.tree_leaves(shapes))
    ref = _load("reference", "nemotron_h_fetchsgd")
    assert d == ref.D == 666_962_944
    assert [int(np.prod(s.shape)) for s in
            jax.tree_util.tree_leaves(shapes)] == list(ref.SIZES)
