"""The looped language model against its plain reference
(``benchmarks/reference/ouro_fetchsgd.py``), at the tiny size: hidden 64, 2
heads of 32, gated MLP of 176, 3 layers run 4 times, vocabulary 256, seeded
weights made by the reference."""

import dataclasses
import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.flatten_util import ravel_pytree

from commefficient_tpu.federated.losses import make_lm_loss
from commefficient_tpu.models.ouro import (Ouro, OuroConfig,
                                           exit_distribution, logits)
from commefficient_tpu.ops.rotary import apply_rotary, rotary_angles

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmarks")
T = 21          # a multiple of no block


def _load(kind, name):
    spec = importlib.util.spec_from_file_location(
        f"tiny_{name}", os.path.join(BENCH, kind, name + ".py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def tiny_model_group(cfg, seq_len=T):
    group = {k: getattr(cfg, k) for k in (
        "layers_held", "hidden_size", "vocab_rows", "rms_norm_eps",
        "num_attention_heads", "num_key_value_heads", "head_dim",
        "intermediate_size", "rope_theta", "total_ut_steps", "entropy_beta")}
    group["seq_len"] = seq_len
    return group


@pytest.fixture(scope="module")
def ref():
    reference = _load("reference", "ouro_fetchsgd")
    reference.configure(tiny_model_group(OuroConfig.tiny()))
    return reference


@pytest.fixture(scope="module")
def tiny(ref):
    """(config, model, flat weights of seed 7, unflatten, ids, labels)."""
    cfg = OuroConfig.tiny()
    model = Ouro(cfg)
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


def _program_grad(model, unflatten, flat, ids, labels, mask):
    loss_fn = make_lm_loss(model, train=True)
    return jax.jit(jax.grad(lambda f: jnp.sum(
        loss_fn(unflatten(f), (ids, labels), None, True)[0] * mask)))(flat)


def _assert_leaves_close(ref, got, want, atol=2e-4):
    for name, a, b in ref.leaf_slices():
        scale = float(jnp.max(jnp.abs(want[a:b]))) + 1e-12
        np.testing.assert_allclose(got[a:b] / scale, want[a:b] / scale,
                                   atol=atol, err_msg=name)


def test_every_exit_matches_the_reference(ref, tiny):
    cfg, model, flat, unflatten, ids, labels = tiny
    with jax.default_matmul_precision("highest"):
        params, p = unflatten(flat), ref.unflatten(flat)
        exits, gate = model.apply({"params": params}, ids)
        want = ref.exit_states(p, ids, "float32")
        assert exits.shape == (cfg.total_ut_steps, 3, T, cfg.hidden_size)
        for t, h in enumerate(want):
            np.testing.assert_allclose(exits[t], h, rtol=2e-4, atol=2e-5)
            np.testing.assert_allclose(
                logits(params, exits[t]),
                ref.logits_fn(p["lm_head_embedding"], h, "float32"),
                rtol=2e-4, atol=2e-5)
        np.testing.assert_allclose(gate, jnp.stack(ref.exit_gates(p, want)),
                                   rtol=1e-5)


def test_exit_distribution_and_loss_match_the_reference(ref, tiny):
    cfg, model, flat, unflatten, ids, labels = tiny
    with jax.default_matmul_precision("highest"):
        params = unflatten(flat)
        _, gate = model.apply({"params": params}, ids)
        dist = exit_distribution(gate)
        np.testing.assert_allclose(jnp.sum(dist, axis=0), 1.0, rtol=1e-6)
        _, want_dist = ref.token_losses(flat, ids, labels, "float32")
        np.testing.assert_allclose(dist, want_dist, rtol=1e-5)
        loss, rows = make_lm_loss(model, train=True)(
            params, (ids, labels), None, True)
        np.testing.assert_allclose(
            loss, ref.sequence_losses(flat, ids, labels, "float32"),
            rtol=1e-5)
        # validation reads the last exit's cross-entropy alone
        val, val_rows = make_lm_loss(model, train=False)(
            params, (ids, labels), None, False)
        last = ref._exit_nll(
            ref.unflatten(flat)["lm_head_embedding"],
            ref.exit_states(ref.unflatten(flat), ids, "float32")[-1],
            labels, labels >= 0, "float32")
        np.testing.assert_allclose(
            val_rows[1], jnp.sum(jnp.where(labels >= 0, last, 0.0), axis=-1),
            rtol=1e-5)
    # loop.expected_steps / loop.tokens: the mean exit depth, in [1, steps]
    assert make_lm_loss(model, train=True).counters == (
        "loop.expected_steps", "loop.tokens")
    assert rows.shape == (2, 3)
    np.testing.assert_array_equal(rows[1], T - 1)
    depth = np.asarray(rows[0] / rows[1])
    assert np.all((depth >= 1.0) & (depth <= cfg.total_ut_steps))


def test_a_gate_at_zero_exits_after_1_875_steps(tiny):
    """lambda = 1/2 everywhere: p = (1/2, 1/4, 1/8, 1/8)."""
    cfg, model, flat, unflatten, ids, labels = tiny
    params = unflatten(flat)
    params["exit_gate"] = jax.tree.map(jnp.zeros_like, params["exit_gate"])
    _, rows = make_lm_loss(model, train=True)(params, (ids, labels), None,
                                              True)
    np.testing.assert_allclose(rows[0] / rows[1], 1.875, rtol=1e-6)
    np.testing.assert_allclose(
        exit_distribution(jnp.full((4, 2), 0.5)),
        np.array([[.5, .5], [.25, .25], [.125, .125], [.125, .125]]))


def test_gradients_match_the_reference_leaf_by_leaf(ref, tiny):
    cfg, model, flat, unflatten, ids, labels = tiny
    mask = jnp.array([1.0, 1.0, 0.0])
    with jax.default_matmul_precision("highest"):
        got = _program_grad(model, unflatten, flat, ids, labels, mask)
        _, want = ref._block_grad(flat, ids, labels, mask, "float32", None)
    _assert_leaves_close(ref, got, want)


def test_the_loop_ties_to_the_unrolled_plain_model(ref, tiny):
    """The gradient of each shared leaf is the sum, over the four steps, of
    the gradients of a 12-layer unrolled copy with untied weights (the
    reference's layers, each step's three and its final norm a copy of their
    own); and d counts each shared leaf once."""
    cfg, model, flat, unflatten, ids, labels = tiny
    S, L = cfg.total_ut_steps, cfg.layers_held
    shared = ref.unflatten(flat)
    looped = {k: v for k, v in shared.items() if k.startswith("loop/")}
    rest = {k: v for k, v in shared.items() if not k.startswith("loop/")}

    def untied_name(name, step):
        if "/layers_" in name:
            i = int(name.split("/layers_")[1][:2])
            return name.replace(f"/layers_{i:02d}/",
                                f"/layers_{step * L + i:02d}/")
        return f"step{step}/{name}"

    untied = {untied_name(k, s): v for k, v in looped.items()
              for s in range(S)}
    assert len(untied) == S * len(looped)

    def unrolled_loss(untied, rest):
        p = {**untied, **rest}
        h, valid, states = p["embed/embedding"][ids], labels >= 0, []
        for s in range(S):
            for i in range(L):
                h = ref.layer(p, h, s * L + i, "float32")
            h = ref._rms(h, p[f"step{s}/loop/final_norm/scale"])
            states.append(h)
        nll = jnp.stack([ref._exit_nll(p["lm_head_embedding"], h, labels,
                                       valid, "float32") for h in states])
        dist = ref.exit_distribution(ref.exit_gates(p, states))
        entropy = -jnp.sum(dist * jnp.log(dist), axis=0)
        loss = jnp.sum(dist * nll, axis=0) - cfg.entropy_beta * entropy
        return jnp.sum(jnp.sum(jnp.where(valid, loss, 0.0), axis=-1)
                       / jnp.sum(valid, axis=-1))

    with jax.default_matmul_precision("highest"):
        got = ref.unflatten(_program_grad(model, unflatten, flat, ids,
                                          labels, jnp.ones(3)))
        g_untied, g_rest = jax.jit(jax.grad(unrolled_loss, argnums=(0, 1)))(
            untied, rest)
    for name in looped:
        want = sum(g_untied[untied_name(name, s)] for s in range(S))
        scale = float(jnp.max(jnp.abs(want))) + 1e-12
        np.testing.assert_allclose(got[name] / scale, want / scale,
                                   atol=2e-4, err_msg=name)
        # the steps' parts differ: the sum is of four, not four of one
        assert not np.allclose(g_untied[untied_name(name, 0)],
                               g_untied[untied_name(name, S - 1)])
    for name in rest:
        scale = float(jnp.max(jnp.abs(g_rest[name]))) + 1e-12
        np.testing.assert_allclose(got[name] / scale, g_rest[name] / scale,
                                   atol=2e-4, err_msg=name)
    d = sum(int(np.prod(v.shape)) for v in shared.values())
    assert d == ref.D == flat.shape[0]


def test_the_two_rematerialisation_arrangements_give_the_same_gradients(
        ref, tiny):
    cfg, model, flat, unflatten, ids, labels = tiny
    other = Ouro(dataclasses.replace(
        cfg, remat="layer" if cfg.remat == "step" else "step"))
    mask = jnp.ones(3)
    got = _program_grad(other, unflatten, flat, ids, labels, mask)
    want = _program_grad(model, unflatten, flat, ids, labels, mask)
    _assert_leaves_close(ref, got, want, atol=1e-5)


def test_the_program_holds_the_stack_once(tiny):
    """A scan over the steps with the parameters broadcast: the traced loss
    holds as many matrix products with 4 steps as with 2."""
    cfg, model, flat, unflatten, ids, labels = tiny

    def products(steps):
        m = Ouro(dataclasses.replace(cfg, total_ut_steps=steps))
        loss_fn = make_lm_loss(m, train=False)
        text = str(jax.make_jaxpr(lambda f: loss_fn(
            unflatten(f), (ids, labels), None, False))(flat))
        return text.count("dot_general")

    assert products(4) == products(2) > 0


@pytest.mark.parametrize("positions", [np.arange(40),
                                       np.array([0, 1, 1023, 2048, 4096])])
def test_rotary_is_the_complex_rotation(ref, positions):
    """Half-split rotary against (x1 + i x2) exp(i pos theta^(-2j/D)), in
    float64 on the host, at positions up to 4096; and against the
    reference's."""
    D, theta = 32, 1e6
    x = np.random.default_rng(0).normal(
        size=(2, len(positions), 3, D)).astype(np.float32)
    cos, sin = rotary_angles(jnp.asarray(positions), D, theta)
    got = apply_rotary(jnp.asarray(x, jnp.bfloat16).astype(jnp.float32), cos,
                       sin)
    assert got.dtype == jnp.float32
    x64 = np.asarray(jnp.asarray(x, jnp.bfloat16).astype(jnp.float32),
                     np.float64)
    z = x64[..., :D // 2] + 1j * x64[..., D // 2:]
    angle = (positions[:, None].astype(np.float64)
             * theta ** (-np.arange(D // 2) / (D // 2)))
    turned = z * np.exp(1j * angle)[None, :, None, :]
    want = np.concatenate([turned.real, turned.imag], axis=-1)
    # float32 angles at position 4096: 4096 * 2^-24 of a radian
    np.testing.assert_allclose(got, want, atol=2e-3)
    np.testing.assert_allclose(
        got, ref.rotary(jnp.asarray(x64, jnp.float32),
                        jnp.asarray(positions)), atol=1e-6)
    # a score depends on the difference of the positions alone
    q = apply_rotary(jnp.asarray(x[:, :1]), *rotary_angles(
        jnp.asarray([7]), D, theta))
    k = apply_rotary(jnp.asarray(x[:, 1:2]), *rotary_angles(
        jnp.asarray([3]), D, theta))
    q2 = apply_rotary(jnp.asarray(x[:, :1]), *rotary_angles(
        jnp.asarray([1004]), D, theta))
    k2 = apply_rotary(jnp.asarray(x[:, 1:2]), *rotary_angles(
        jnp.asarray([1000]), D, theta))
    np.testing.assert_allclose(jnp.sum(q * k, -1), jnp.sum(q2 * k2, -1),
                               atol=2e-3)


def test_one_sketch_round_through_train_equals_the_reference(ref, tiny,
                                                            tmp_path):
    """``training.gpt2.train`` (the same FedLearner, round and server as
    GPT2 and nemotron_h) for two rounds against ``reference.steps`` on the
    same batches."""
    from commefficient_tpu.federated.api import FedLearner
    from commefficient_tpu.training import gpt2
    from commefficient_tpu.utils import tracing
    _load("datagen", "token_docs").write(
        str(tmp_path), seed=3, num_clients=8, tokens_per_client=4 * 32,
        valid_tokens=4 * 32, vocab_rows=256)
    ref.configure(tiny_model_group(OuroConfig.tiny(), seq_len=32))
    argv = ["--model", "ouro-tiny", "--dataset_name", "TOKENS",
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
    try:
        assert len(seen) == 2 and learner.cfg.grad_size == ref.D
        spec = {"mode": "sketch", "k": 200, "num_rows": 5, "num_cols": 4000,
                "virtual_momentum": 0.9, "weight_decay": 0.0,
                "num_workers": 4, "lr_scale": 0.05,
                "total_steps": int(learner.lr_schedule.knots[1])}
        batches = [(c[0].reshape(-1, 32), c[1].reshape(-1), m.reshape(-1))
                   for c, m in seen]
        with jax.default_matmul_precision("highest"):
            want = ref.steps(w0, batches, spec, "float32")
            dropped = ref.steps(w0, batches, spec, "float32",
                                fault="single_pass")
        np.testing.assert_allclose(np.asarray(learner.state.weights),
                                   want["w"], rtol=1e-4, atol=1e-7)
        assert abs(row["train_loss"] - np.mean(want["loss"])) < 1e-4
        # a program that dropped the loop would have computed another loss
        assert abs(row["train_loss"] - np.mean(dropped["loss"])) > 1e-2
        counts = tracing.snapshot()["counters"]
        tokens = counts["loop.tokens"][0]
        assert tokens == 2 * 4 * 31
        assert 1.0 <= counts["loop.expected_steps"][0] / tokens <= 4.0
        assert row["vocab"] == 256
    finally:
        ref.configure(tiny_model_group(OuroConfig.tiny()))


@pytest.mark.parametrize("argv, match", [
    (["--model", "ouro-tiny"], "TOKENS"),
    (["--model", "gpt2-tiny", "--dataset_name", "TOKENS"], "ouro"),
    (["--model", "ouro-tiny", "--dataset_name", "TOKENS",
      "--layer_pattern", "EM*"], "layers_held"),
    (["--model", "nemotron_h-tiny", "--dataset_name", "TOKENS",
      "--layers_held", "2"], "layer_pattern"),
])
def test_a_model_and_a_flag_that_do_not_go_together_are_refused(
        tmp_path, argv, match):
    from commefficient_tpu.training import gpt2
    _load("datagen", "token_docs").write(
        str(tmp_path), seed=3, num_clients=2, tokens_per_client=64,
        valid_tokens=64, vocab_rows=256)
    args = gpt2.build_gpt2_parser().parse_args(
        argv + ["--dataset_dir", str(tmp_path), "--max_seq_len", "32",
                "--num_workers", "2", "--local_batch_size", "1"])
    with pytest.raises(ValueError, match=match):
        gpt2.train(args, max_rounds=1, log=False)


def test_the_cut_key_and_nothing_else_changes_the_parameter_count():
    """The benchmark's cut (8 of 48 layers) at published widths, counted
    from shapes alone: every shared leaf once."""
    cfg = OuroConfig(layers_held=8)
    assert (cfg.hidden_size, cfg.num_attention_heads,
            cfg.num_key_value_heads, cfg.head_dim, cfg.intermediate_size,
            cfg.vocab_size, cfg.total_ut_steps) == (2048, 16, 16, 128, 5632,
                                                    49152, 4)
    shapes = jax.eval_shape(Ouro(cfg).init, jax.random.PRNGKey(0),
                            jnp.zeros((1, 128), jnp.int32))["params"]
    sizes = [int(np.prod(s.shape))
             for s in jax.tree_util.tree_leaves(shapes)]
    ref = _load("reference", "ouro_fetchsgd")
    assert sum(sizes) == ref.D == 612_438_017
    assert sizes == list(ref.SIZES)
    assert ref.flops_per_sample() == 3 * 2048 * 4 * (
        8 * (4 * 2 * 2048 * 2048 + 2 * 16 * 128 * 2048 + 6 * 2048 * 5632)
        + 2 * 2048 * 49152 + 2 * 2048)
