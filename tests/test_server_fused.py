"""--server_fused contract: the fused server-update path (streaming
top-k Pallas kernel + unsketch/momentum/error-feedback epilogue,
ops/topk_kernels.py) is a PERFORMANCE switch, not a semantics switch.

Driven through the real jitted round program (build_round_step), the
fused path must reproduce the incumbent ``--server_fused off`` chain
BITWISE — weights, Vvelocity, Verror — over a multi-round trajectory,
for every server mode that selects (sketch, true_topk, local_topk),
under BOTH force_dispatch modes, with each program's compile cache
staying at exactly one entry.  The op-level bit-identity (kernel vs
jax.lax.top_k, ties, per-row k) is pinned in tests/test_topk_kernels.py;
this file pins the END-TO-END wiring: server.py dispatch, the
countsketch fused unsketch, and the het-k client path.
"""

import contextlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from commefficient_tpu.config import FedConfig
from commefficient_tpu.ops.sketch_kernels import force_dispatch

MODE_CFGS = {
    "true_topk": dict(mode="true_topk", error_type="virtual", k=3,
                      virtual_momentum=0.9),
    "local_topk": dict(mode="local_topk", error_type="local", k=3,
                       local_momentum=0.9, virtual_momentum=0.9),
    "sketch": dict(mode="sketch", error_type="virtual", k=3, num_rows=3,
                   num_cols=256, virtual_momentum=0.9),
}


def _run_rounds(cfg_kw, *, server_fused, force=None, rounds=4):
    """Drive the real jitted round program for ``rounds`` rounds and
    return (weights, Vvelocity, Verror, compile_cache_size).  ``force``
    wraps trace AND drives in one force_dispatch context, so the
    compiled program is the forced arm, not a mid-trajectory mix."""
    from commefficient_tpu.federated.losses import make_cv_loss
    from commefficient_tpu.federated.round import (build_round_step,
                                                   init_fed_state)
    from commefficient_tpu.models import TinyMLP
    from commefficient_tpu.utils.params import flatten_params

    model = TinyMLP(num_classes=2, hidden=6)
    rng = np.random.RandomState(0)
    W, B = 3, 5
    Xs = rng.randn(rounds, W, B, 4).astype(np.float32)
    ys = (Xs[:, :, :, 0] > 0).astype(np.int32)
    mask = np.ones((W, B), np.float32)
    mask[2, 3:] = 0.0

    params = model.init(jax.random.PRNGKey(3), Xs[0, 0][:1],
                        train=False)["params"]
    flat, unflatten = flatten_params(params)
    cfg = FedConfig(num_workers=W, num_clients=4, lr_scale=0.1,
                    weight_decay=0, server_fused=server_fused,
                    **cfg_kw).finalize(int(flat.shape[0]))
    step = build_round_step(make_cv_loss(model), unflatten, cfg)
    state = init_fed_state(cfg, jnp.asarray(np.asarray(flat)))
    ctx = force_dispatch(force) if force else contextlib.nullcontext()
    with ctx:
        for r in range(rounds):
            ids = np.array([r % 4, (r + 1) % 4, (r + 2) % 4])
            ks = ()
            if cfg.client_k_active:
                from commefficient_tpu.federated.faults import \
                    cohort_client_ks
                ks = (jnp.asarray(cohort_client_ks(
                    11, ids, cfg.k, cfg.client_k_dist)),)
            state, _ = step(state, jnp.asarray(ids),
                            (jnp.asarray(Xs[r]), jnp.asarray(ys[r])),
                            jnp.asarray(mask), 0.1,
                            jax.random.PRNGKey(7 + r), *ks)
        # read INSIDE the context: force_dispatch clears jit caches on
        # exit (a cached program from the other mode must not leak out)
        cache = step._cache_size()
    return (np.asarray(state.weights), np.asarray(state.opt.Vvelocity),
            np.asarray(state.opt.Verror), cache)


@pytest.mark.parametrize("force", ["kernel", "fallback"])
@pytest.mark.parametrize("mode", sorted(MODE_CFGS))
def test_round_trajectory_bitwise_fused_vs_incumbent(mode, force):
    """server_fused=auto under force_dispatch(force) == server_fused=off
    incumbent, bitwise, over 4 rounds — and neither program retraces."""
    w_f, v_f, e_f, cache_f = _run_rounds(MODE_CFGS[mode],
                                         server_fused="auto", force=force)
    w_i, v_i, e_i, cache_i = _run_rounds(MODE_CFGS[mode],
                                         server_fused="off")
    np.testing.assert_array_equal(w_f, w_i)
    np.testing.assert_array_equal(v_f, v_i)
    np.testing.assert_array_equal(e_f, e_i)
    assert cache_f == 1 and cache_i == 1


@pytest.mark.parametrize("mode", ["true_topk", "sketch"])
def test_server_update_unit_bitwise_and_kernel_in_jaxpr(mode):
    """server_update alone: the forced-kernel program contains the
    streaming pallas_calls, the forced-fallback program contains none,
    and a 6-step (gradient, state) trajectory agrees bitwise."""
    from commefficient_tpu.federated.server import (init_server_opt_state,
                                                    make_sketch,
                                                    server_update)

    d, k = 3000, 7
    kw = dict(MODE_CFGS[mode])
    kw["k"] = k
    cfg = FedConfig(**kw).finalize(d)
    sketch = make_sketch(cfg) if mode == "sketch" else None

    def fn(g, st):
        return server_update(g, st, cfg, 0.1, sketch=sketch)

    rng = np.random.RandomState(1)
    grads = rng.randn(6, d).astype(np.float32)
    if mode == "sketch":
        grads = np.stack([np.asarray(sketch.sketch_vec(jnp.asarray(g)))
                          for g in grads])

    outs = {}
    for f in ("kernel", "fallback"):
        with force_dispatch(f):
            jaxpr = str(jax.make_jaxpr(fn)(jnp.asarray(grads[0]),
                                           init_server_opt_state(cfg)))
            assert ("pallas_call" in jaxpr) == (f == "kernel"), f
            jitted = jax.jit(fn)
            st = init_server_opt_state(cfg)
            traj = []
            for g in grads:
                upd, st = jitted(jnp.asarray(g), st)
                traj.append((np.asarray(upd), np.asarray(st.Vvelocity),
                             np.asarray(st.Verror)))
            assert jitted._cache_size() == 1
            outs[f] = traj
    for step_k, step_f in zip(outs["kernel"], outs["fallback"]):
        for a, b in zip(step_k, step_f):
            np.testing.assert_array_equal(a, b)


@pytest.mark.parametrize("force", ["kernel", "fallback"])
def test_het_k_round_trajectory_bitwise(force):
    """--client_k_dist heterogeneous clients ride the batched per-row-k
    kernel inside the round vmap; the forced-kernel trajectory must
    match the pure-XLA one bitwise (the XLA arm is itself pinned
    trajectory-identical to the legacy two-stage masking at the op level
    in tests/test_topk_kernels.py)."""
    if force == "kernel":
        got = _run_rounds(dict(MODE_CFGS["local_topk"],
                               client_k_dist="uniform:0.3,1.0"),
                          server_fused="auto", force="kernel")
        ref = _run_rounds(dict(MODE_CFGS["local_topk"],
                               client_k_dist="uniform:0.3,1.0"),
                          server_fused="auto", force="fallback")
        for a, b in zip(got[:3], ref[:3]):
            np.testing.assert_array_equal(a, b)
        assert got[3] == 1 and ref[3] == 1
    else:
        # off == fallback: the flag only ever selects between programs
        # that are bitwise-equal, so "off" is purely a debug pin.
        got = _run_rounds(dict(MODE_CFGS["local_topk"],
                               client_k_dist="uniform:0.3,1.0"),
                          server_fused="off")
        ref = _run_rounds(dict(MODE_CFGS["local_topk"],
                               client_k_dist="uniform:0.3,1.0"),
                          server_fused="auto", force="fallback")
        for a, b in zip(got[:3], ref[:3]):
            np.testing.assert_array_equal(a, b)


def _sketch_server_jaxpr(arm, d=3000, k=7):
    """Walk the traced sketch-mode ``server_update`` of one arm:
    (pallas_call equations, jitted callees' names, [(primitive, operand
    size)] of every cumsum / sort / top_k / scatter, the table's shape)."""
    from commefficient_tpu.analysis.walker import walk
    from commefficient_tpu.federated.server import (init_server_opt_state,
                                                    make_sketch,
                                                    server_update)

    cfg = FedConfig(**dict(MODE_CFGS["sketch"], k=k),
                    server_fused="off" if arm == "off" else "auto"
                    ).finalize(d)
    sketch = make_sketch(cfg)

    def fn(g, st):
        return server_update(g, st, cfg, 0.1, sketch=sketch)

    ctx = (contextlib.nullcontext() if arm == "off"
           else force_dispatch(arm))
    with ctx:
        jaxpr = jax.make_jaxpr(fn)(jnp.zeros(cfg.transmit_shape),
                                   init_server_opt_state(cfg))
    sites, _ = walk(jaxpr)
    kernels, callees, movers = [], [], []
    for site in sites:
        prim, params = site.primitive, site.eqn.params
        if prim == "pallas_call":
            kernels.append(site.eqn)
        elif prim in ("pjit", "jit"):
            callees.append(params["name"])
        elif (prim in ("cumsum", "sort", "top_k")
              or prim.startswith("scatter")):
            movers.append((prim, max(
                int(np.prod(v.aval.shape)) for v in site.eqn.invars
                if hasattr(v.aval, "shape"))))
    return kernels, callees, movers, cfg.transmit_shape


@pytest.mark.parametrize("arm", ["kernel", "fallback", "off"])
def test_sketch_server_update_arms_in_jaxpr(arm):
    """Where the fused unsketch dispatches, the sketch server estimates
    the d coordinates once, counts and selects over that one buffer,
    takes the select kernel's dense output as the update and one more
    pass of the dense sketch kernel as its support: the table feeds one
    kernel, nothing pads or slices the estimates on their way to the
    select (which overwrites them and writes no mask), and there is no
    d-long cumsum / sort / scatter and no sparse re-sketch.  Everywhere
    else no kernel runs and the ``lax.top_k`` -> scatter ->
    ``sketch_sparse`` chain stands."""
    d = 3000
    kernels, callees, movers, table_shape = _sketch_server_jaxpr(arm, d=d)
    d_long = [(p, n) for p, n in movers if n >= d]
    if arm != "kernel":
        assert not kernels, kernels
        assert "sketch_sparse" in callees
        assert ("top_k", d) in d_long, movers
        assert any(p.startswith("scatter") for p, _ in d_long), movers
        return
    names = [e.params["name"] for e in kernels]
    # the radix loop's body and the final count are one eqn each
    assert sorted(names) == ["estimates_pallas", "radix_count_pallas",
                             "radix_count_pallas", "sketch_vec_pallas",
                             "unsketch_select_pallas"], names
    assert not d_long, d_long
    assert "sketch_sparse" not in callees
    reads_table = [e.params["name"] for e in kernels
                   if any(v.aval.shape == table_shape for v in e.invars)]
    assert reads_table == ["estimates_pallas"]
    by_name = {e.params["name"]: e for e in kernels}
    (est,) = by_name["estimates_pallas"].outvars
    assert est.aval.size >= d and est.aval.dtype == jnp.float32
    # the very array the estimates pass wrote: no pad, no slice between
    select = by_name["unsketch_select_pallas"]
    assert select.invars[0] is est
    assert [v.aval.shape for v in select.outvars] == [est.aval.shape]
    assert tuple(select.params["input_output_aliases"]) == ((0, 0),)
    counts = [e for e in kernels
              if e.params["name"] == "radix_count_pallas"]
    assert sum(e.invars[0] is est for e in counts) == 1   # the final count
    assert all(e.invars[0].aval.shape == est.aval.shape for e in counts)


@pytest.mark.parametrize("seed", [0, 1, 2, "planted"])
def test_dense_resketch_support_equals_sparse_at_cell_density(seed,
                                                             planted_table):
    """The fused arm's two substitutions at the sketch cell's density
    (k/c = 0.1, r = 5), through the kernels themselves: the select
    kernel's dense output is bitwise ``zeros(d).at[idxs].set(vals)``,
    and the dense re-sketch's nonzero buckets are ``sketch_sparse``'s,
    bucket for bucket (only ``!= 0`` is read; the sums may differ in
    their last bits where nonzeros collide)."""
    from commefficient_tpu.federated.server import make_sketch
    from commefficient_tpu.ops.topk import topk_values_indices

    d, c, r, k = 656_864, 50_000, 5, 5_000
    cs = make_sketch(FedConfig(mode="sketch", error_type="virtual", k=k,
                               num_rows=r, num_cols=c).finalize(d))
    planted = seed == "planted"
    rng = np.random.RandomState(7 if planted else seed)
    if planted:
        table = planted_table(cs, rng)
    else:
        table = cs.sketch_vec(jnp.asarray(rng.randn(d).astype(np.float32)))

    vals, idxs = topk_values_indices(cs.estimates(table), k,
                                     use_kernel=False)
    ref_update = np.asarray(jnp.zeros((d,)).at[idxs].set(vals))
    ref_support = np.asarray(cs.sketch_sparse(vals, idxs) != 0)
    with force_dispatch("kernel"):
        update = cs.unsketch(table, k, None, use_kernel=True)
        support = np.asarray(
            cs.sketch_vec_batched(update, use_kernel=True) != 0)
    update = np.asarray(update)

    np.testing.assert_array_equal(update.view(np.uint32),
                                  ref_update.view(np.uint32))
    np.testing.assert_array_equal(support, ref_support)
    assert ref_support.any() and not ref_support.all()
    if planted:
        picked = np.asarray(vals)
        assert (picked == 0).any() and np.signbit(picked[picked == 0]).any()
        assert np.unique(np.abs(picked[picked != 0])).size < 100
    else:
        assert np.count_nonzero(update) == k
