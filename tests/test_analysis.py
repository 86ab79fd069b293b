"""Unit tests for the graft-audit analysis subsystem: walker descent,
mutation (golden-violation) programs, rule behavior, retrace guard, and
the PRNG lint.

The mutation tests are the analyzer's own regression suite: each one
reintroduces a defect this repo already paid to remove — the O(W·d)
dense changed-matrix (PR 2) and materialized (B, H, T, T) attention
scores (PR 3) — and asserts the footprint rule FAILS it, so a future
refactor cannot silently revert those contracts without tripping a test.
"""

import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from commefficient_tpu import analysis as A
from commefficient_tpu.federated.round import download_counts


# --------------------------------------------------------------------------
# walker descent
# --------------------------------------------------------------------------

def test_walker_descends_custom_vjp_and_remat():
    """The acceptance criterion of the subsystem: the walk reaches eqns
    inside custom_vjp and remat sub-jaxprs (the old test-local walker
    was blind to both)."""

    @jax.custom_vjp
    def f(x):
        return jnp.sin(x) * 2.0

    f.defvjp(lambda x: (f(x), x), lambda res, g: (g * 2.0 * jnp.cos(res),))

    @jax.checkpoint
    def g(x):
        return jnp.tanh(f(x)).sum()

    closed = jax.make_jaxpr(jax.grad(g))(jnp.ones((4,)))
    _, stats = A.walk(closed)
    assert stats.visited("remat2"), stats.descended_into
    # inside the remat body, the (un-differentiated) custom_vjp call is
    # still a custom_vjp_call eqn whose call_jaxpr we must enter
    assert any("custom_vjp" in p for p in stats.descended_into), \
        stats.descended_into
    # and the sin inside f's fun_jaxpr was actually visited
    prims = {s.primitive for s in A.iter_eqns(closed)}
    assert "sin" in prims


def test_walker_path_strings_nest():
    def body(c, x):
        return c + jnp.sum(jnp.outer(x, x)), c

    def f(xs):
        out, _ = jax.lax.scan(body, 0.0, xs)
        return out

    sites = list(A.iter_eqns(jax.make_jaxpr(f)(jnp.ones((3, 5)))))
    assert any(s.path.startswith("scan") for s in sites)


def test_collect_shapes_matches_legacy_behavior():
    def f(a, b):
        return jnp.dot(a, b)

    shapes = A.collect_shapes(jax.make_jaxpr(f)(jnp.ones((3, 5)),
                                                jnp.ones((5, 7))))
    assert (3, 7) in shapes


# --------------------------------------------------------------------------
# mutation tests: golden violations
# --------------------------------------------------------------------------

def test_mutation_dense_changed_matrix_fails():
    """Golden violation (a): the O(W·d) accounting changed-matrix that
    PR 2 removed.  Reintroducing it must fail the footprint rule."""
    d, w = 46, 3

    def dense_accounting(last_changed, stale):
        changed = last_changed[None, :] >= stale[:, None]   # (W, d) !!
        return jnp.sum(changed, axis=1)

    rep = A.audit(dense_accounting, jnp.zeros((d,), jnp.int32),
                  jnp.zeros((w,), jnp.int32), dims={"W": w, "d": d})
    assert not rep.ok
    fp = rep.rule("footprint")
    assert any(v.shape in ((w, d), (d, w)) for v in fp.violations)


def test_mutation_materialized_attention_scores_fails():
    """Golden violation (b): materialized (B, H, T, T) attention scores
    — the thing the flash kernels exist to keep out of HBM."""
    B, H, T, D = 2, 4, 64, 8

    def naive_attention(q, k, v):
        scores = jnp.einsum("bhtd,bhsd->bhts", q, k) / np.sqrt(D)
        probs = jax.nn.softmax(scores, axis=-1)            # (B,H,T,T) !!
        return jnp.einsum("bhts,bhsd->bhtd", probs, v)

    args = [jnp.ones((B, H, T, D)) for _ in range(3)]
    rep = A.audit(naive_attention, *args, dims={"B": B, "H": H, "T": T})
    assert not rep.ok
    assert any(v.shape == (B, H, T, T)
               for v in rep.rule("footprint").violations)


def test_clean_program_passes():
    """The streamed-comparison accounting — the shape the contract
    demands — audits clean under the same dims."""
    d, w = 46, 3
    rep = A.audit(download_counts, jnp.zeros((d,), jnp.int32),
                  jnp.zeros((w,), jnp.int32), dims={"W": w, "d": d})
    assert rep.ok, [str(v) for v in rep.violations]


# --------------------------------------------------------------------------
# rule behavior
# --------------------------------------------------------------------------

def test_footprint_byte_budget():
    def f(x):
        return jnp.outer(x, x).sum()

    rule = A.FootprintRule((), max_eqn_bytes=1000)
    rep = A.audit(f, jnp.ones((100,)), rules=[rule])
    assert not rep.ok   # the (100, 100) f32 outer product is 40 kB
    assert "budget" in rep.violations[0].message


def test_footprint_scatter_writeback_allowed():
    """(num_clients, d) state writeback via scatter is legitimate; a
    broadcasted dense compute at the same shape is not."""
    n, d = 7, 46

    def writeback(state, rows, ids):
        return state.at[ids].set(rows, mode="drop")

    rep = A.audit(writeback, jnp.zeros((n, d)), jnp.ones((3, d)),
                  jnp.arange(3), dims={"num_clients": n, "d": d})
    assert rep.ok, [str(v) for v in rep.violations]

    def dense(state, rows, ids):
        return state * 2.0                                  # (n, d) compute

    rep2 = A.audit(dense, jnp.zeros((n, d)), jnp.ones((3, d)),
                   jnp.arange(3), dims={"num_clients": n, "d": d})
    assert not rep2.ok


def test_transfer_rule_flags_callbacks():
    def f(x):
        y = jnp.sin(x)
        return jax.pure_callback(
            lambda a: np.asarray(a) * 2.0,
            jax.ShapeDtypeStruct(x.shape, x.dtype), y)

    rep = A.audit(f, jnp.ones((4,)))
    tr = rep.rule("transfer")
    assert not tr.ok
    assert tr.violations[0].primitive == "pure_callback"


def test_dtype_rule_flags_large_f32_in_bf16_region():
    n = 512 * 512  # > min_elements

    def f(x):
        big = x.astype(jnp.float32)
        y = jnp.where(big > 0, big, big * 2.0)   # select_n is allowed...
        z = jnp.sign(y)                          # ...sign is not
        return z.astype(jnp.bfloat16)

    rep = A.audit(f, jnp.ones((n,), jnp.bfloat16), bf16=True)
    dt = rep.rule("dtype")
    assert not dt.ok and any(v.primitive == "sign" for v in dt.violations)

    def softmaxish(x):
        h = x.astype(jnp.float32)
        e = jnp.exp(h - jnp.max(h))
        return (e / jnp.sum(e)).astype(jnp.bfloat16)

    rep2 = A.audit(softmaxish, jnp.ones((n,), jnp.bfloat16), bf16=True)
    assert rep2.rule("dtype").ok, \
        [str(v) for v in rep2.rule("dtype").violations]


# --------------------------------------------------------------------------
# retrace guard
# --------------------------------------------------------------------------

def test_retrace_guard_passes_stable_fn():
    jitted = jax.jit(lambda x: x * 2.0)
    rep = A.check_retrace(jitted, lambda i: (jnp.ones((8,)) * i,))
    assert rep.ok


def test_retrace_guard_detects_recompiles():
    jitted = jax.jit(lambda x: x * 2.0)
    # a growing shape retraces on every call — the guard must see it
    rep = A.check_retrace(jitted, lambda i: (jnp.ones((8 + i,)),))
    assert not rep.ok
    assert "cache grew" in rep.violations[0].message


# --------------------------------------------------------------------------
# PRNG lint
# --------------------------------------------------------------------------

def _lint_src(tmp_path, src):
    f = tmp_path / "m.py"
    f.write_text(textwrap.dedent(src))
    return A.lint_paths([f])


def test_prng_lint_flags_double_consumption(tmp_path):
    rep = _lint_src(tmp_path, """
        import jax
        def f(key, shape):
            a = jax.random.normal(key, shape)
            b = jax.random.uniform(key, shape)
            return a + b
    """)
    assert not rep.ok
    assert "consumed again" in rep.violations[0].message


def test_prng_lint_accepts_split_and_fold_in(tmp_path):
    rep = _lint_src(tmp_path, """
        import jax
        def f(key, shape):
            k1, key = jax.random.split(key)
            a = jax.random.normal(k1, shape)
            k2 = jax.random.fold_in(key, 1)
            b = jax.random.uniform(k2, shape)
            return a + b
    """)
    assert rep.ok, [str(v) for v in rep.violations]


def test_prng_lint_branch_aware_early_return(tmp_path):
    # the ops/dropout.py shape: two samplers on exclusive paths
    rep = _lint_src(tmp_path, """
        import jax
        def f(key, shape, fast):
            if fast:
                return jax.random.bits(key, shape)
            return jax.random.bernoulli(key, 0.5, shape)
    """)
    assert rep.ok, [str(v) for v in rep.violations]


def test_prng_lint_flags_loop_reuse(tmp_path):
    rep = _lint_src(tmp_path, """
        import jax
        def f(key, xs):
            out = []
            for x in xs:
                out.append(jax.random.normal(key, x.shape))
            return out
    """)
    assert not rep.ok


def test_prng_lint_loop_with_split_ok(tmp_path):
    # the gpt2_generate decode-loop idiom
    rep = _lint_src(tmp_path, """
        import jax
        def f(key, xs):
            out = []
            for x in xs:
                key, sub = jax.random.split(key)
                out.append(jax.random.normal(sub, x.shape))
            return out
    """)
    assert rep.ok, [str(v) for v in rep.violations]


def test_prng_lint_pragma_suppresses(tmp_path):
    rep = _lint_src(tmp_path, """
        import jax
        def f(key, shape):
            a = jax.random.normal(key, shape)
            b = jax.random.normal(key, shape)  # prng-ok: recompute mask
            return a + b
    """)
    assert rep.ok, [str(v) for v in rep.violations]


def test_prng_lint_repo_is_clean():
    """models/, federated/, ops/ carry no key-reuse findings at HEAD —
    the standing hygiene gate the CLI also enforces (--prng-lint)."""
    from pathlib import Path
    import commefficient_tpu

    pkg = Path(commefficient_tpu.__file__).parent
    rep = A.lint_paths([pkg / "models", pkg / "federated", pkg / "ops"])
    assert rep.ok, [str(v) for v in rep.violations]
