"""Test harness config: run everything on a virtual 8-device CPU mesh.

XLA_FLAGS must be set before jax initializes its backends (SURVEY.md §4:
simulated multi-client tests on CPU via
--xla_force_host_platform_device_count). Tests never touch the chip: the
platform is forced to the CPU here as well as by the ``JAX_PLATFORMS=cpu``
the tier-1 command sets, so a bare ``pytest`` on a machine that has a TPU
stays off it too.
"""

import os

flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8").strip()

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_enable_x64", False)
# entry points place a persistent compile cache under the checkout
# (utils/compile_cache.py); tests that call them must not fill it with
# CPU programs, nor read one another's
jax.config.update("jax_enable_compilation_cache", False)

# Every federated round dispatched anywhere in the suite runs under
# jax.transfer_guard("disallow") — an implicit host<->device transfer at
# round-dispatch time (python scalar, stray numpy array) fails the test
# that triggered it.  Scoped around the dispatch (federated/api.py), not
# process-wide: a global disallow would reject ordinary host-side setup.
from commefficient_tpu.federated import api as _fed_api  # noqa: E402

_fed_api.set_transfer_guard("disallow")


import pytest  # noqa: E402


@pytest.fixture(scope="session")
def serving_tiny_engine():
    """ONE tiny byte-tokenizer DecodeEngine shared by the serving test
    modules (test_paged_serving, test_speculative). Engine jits are
    per-instance, so sharing the instance shares every warm program —
    prefill, step, pack, and the solo-generate reference — across the
    files instead of recompiling them per module. A test that asserts
    exact compile counts clears the jits it counts first: under xdist any
    file may have warmed them."""
    import numpy as np

    from commefficient_tpu.data.tokenizer import ByteTokenizer
    from commefficient_tpu.models.gpt2 import GPT2Config, GPT2DoubleHeads
    from commefficient_tpu.serving import DecodeEngine
    tok = ByteTokenizer()
    cfg = GPT2Config.tiny(vocab_size=tok.vocab_size)
    model = GPT2DoubleHeads(cfg)
    ids = np.zeros((1, 1, 8), np.int32)
    params = model.init(jax.random.PRNGKey(0), ids, ids,
                        np.zeros((1, 1), np.int32), train=False)["params"]
    eos = tok.convert_tokens_to_ids("<eos>")
    engine = DecodeEngine(model, params, eos_id=eos, max_len=48,
                          method="greedy")
    return tok, model, params, engine


@pytest.fixture(scope="session")
def gpt2_small_shapes():
    """GPT2-small as served (124 M parameters, vocabulary 50 262, bf16
    compute, a 128-token prompt + 64 new tokens) as SHAPES, for the
    serving trace gates: ``.engine(method)`` is a ``DecodeEngine`` over
    the ``jax.eval_shape`` of the model's init (no weight is ever made),
    ``.abstract_params(model)`` that init for another model (a drafter),
    ``.P`` / ``.N`` the prompt length and the new tokens."""
    import types

    import jax.numpy as jnp

    from commefficient_tpu.models.gpt2 import GPT2Config, GPT2DoubleHeads
    from commefficient_tpu.serving import DecodeEngine
    P, N = 128, 64
    cfg = GPT2Config.small(vocab_size=50262)
    cfg.n_positions = max(cfg.n_positions, P + N)
    cfg.dropout = 0.0
    cfg.dtype = "bfloat16"
    model = GPT2DoubleHeads(cfg)

    def abstract_params(m):
        z = jnp.zeros((1, 1, 8), jnp.int32)
        return jax.eval_shape(
            lambda r: m.init(r, z, z, jnp.zeros((1, 1), jnp.int32),
                             train=False), jax.random.PRNGKey(0))["params"]

    params = abstract_params(model)
    return types.SimpleNamespace(
        P=P, N=N, abstract_params=abstract_params,
        engine=lambda method="greedy": DecodeEngine(
            model, params, eos_id=cfg.vocab_size - 1, max_len=P + N,
            method=method))


@pytest.fixture(scope="session")
def paged_shapes():
    """``make(engine, slots, prefill_len, page_size=16, kv_quant="none")``
    -> (pager, pools, page table, (slots,) int32, (slots,) bool): the
    host pager of a paged server at that size and the shapes of what its
    paged step takes, the pools through ``jax.eval_shape``."""
    import jax.numpy as jnp

    from commefficient_tpu.serving import PagedKVCache

    def make(engine, slots, prefill_len, page_size=16, kv_quant="none"):
        pager = PagedKVCache(slots=slots, max_len=engine.max_len,
                             prefill_len=prefill_len, page_size=page_size)
        pools = jax.eval_shape(lambda: engine.init_paged_pools(
            pager.num_pages, page_size, kv_quant=kv_quant))
        return (pager, pools,
                jax.ShapeDtypeStruct((slots, pager.max_pages), jnp.int32),
                jax.ShapeDtypeStruct((slots,), jnp.int32),
                jax.ShapeDtypeStruct((slots,), jnp.bool_))
    return make


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "audit: jaxpr-level invariant audits (graft-audit gate); "
        "runnable standalone via -m audit")


def pytest_sessionstart(session):
    assert jax.devices()[0].platform == "cpu", (
        "tests must run on CPU; got " + str(jax.devices()))


@pytest.fixture
def uint8_pool(tmp_path):
    """Factory of train sets of random uint8 images in the prepared-array
    layout (CIFAR's: one array a natural client, target = client):
    ``make(sizes, image, transform, **FedDataset keywords)``. Same sizes
    and image shape, same pixels."""
    import numpy as np

    from commefficient_tpu.data.fed_dataset import PreparedArrayDataset

    def make(sizes, image=(32, 32, 3), transform=None, **kw):
        class Pool(PreparedArrayDataset):
            name = "pool"

            def _make_xy(self):
                rng = np.random.RandomState(len(sizes))
                y = np.repeat(np.arange(len(sizes)), sizes)
                x = rng.randint(0, 256, (len(y),) + image).astype(np.uint8)
                return x, y, x[:2], y[:2], len(sizes)

        name = "-".join(map(str, (*sizes, *image)))
        return Pool(dataset_dir=str(tmp_path / name), train=True,
                    transform=transform, **kw)
    return make


@pytest.fixture
def same_rng_state():
    """``same(rng_a, rng_b)``: two ``RandomState``s at the same point of
    the same stream."""
    import numpy as np

    def same(rng_a, rng_b):
        a, b = rng_a.get_state(), rng_b.get_state()
        return (np.array_equal(a[1], b[1])
                and (a[0], *a[2:]) == (b[0], *b[2:]))
    return same


@pytest.fixture
def trace_round():
    """``trace(learner, ids, batch, mask, scan_rounds=None)``: the
    learner's jitted round (with its gathered rows where client state is
    offloaded) and, with ``scan_rounds=K``, the K-round scan dispatch,
    through ``jax.eval_shape`` with the argument plumbing of
    ``train_round_async`` / ``train_rounds_scan``: nothing compiles or
    runs, a drifted signature, shape or dtype raises. Returns the
    (state, metrics) shapes of the round, then of the scan."""
    import jax.numpy as jnp
    import numpy as np

    def trace(learner, ids, batch, mask, scan_rounds=None):
        ids = np.asarray(ids)
        ids_d = jnp.asarray(ids, jnp.int32)
        cols = tuple(jnp.asarray(t) for t in batch)
        m = jnp.asarray(mask, jnp.float32)
        lr = jnp.float32(learner.lr_at(0.0))
        rng = jax.random.PRNGKey(0)
        rows = ((learner._offload_pipe.gather(ids.astype(np.int64)),)
                if learner._offload else ())
        out = jax.eval_shape(learner._round, learner.state, *rows, ids_d,
                             cols, m, lr, rng)
        if not scan_rounds:
            return out
        K = scan_rounds
        stack = lambda a: jnp.broadcast_to(a, (K,) + a.shape)  # noqa: E731
        return out, jax.eval_shape(
            learner._rounds_scan_fn(), learner.state, stack(ids_d),
            tuple(stack(c) for c in cols), stack(m),
            jnp.zeros((K,), jnp.float32), jnp.stack([rng] * K))
    return trace


@pytest.fixture
def planted_table():
    """``plant(cs, rng, n=2000)``: a table whose estimates hold fewer than
    k nonzeros, many of equal magnitude: the top-k then fills up with zero
    estimates in index order, about half of them -0.0 (a negative sign
    times 0.0)."""
    import jax.numpy as jnp
    import numpy as np

    def plant(cs, rng, n=2000):
        vec = np.zeros(cs.d, np.float32)
        at = rng.choice(cs.d, n, replace=False)
        vec[at] = rng.choice(np.float32([0.5, -0.5, 1.25, -1.25, 3.0]), n)
        return cs.sketch_vec(jnp.asarray(vec))
    return plant
