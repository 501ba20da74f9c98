"""Test harness for tpudl.

The reference runs its whole "distributed" suite on local[*] Spark
(SURVEY.md §4: driver+executors in one JVM). Our equivalent trick: an
8-device simulated CPU mesh via XLA host-platform device multiplexing,
so every collective/sharding path is exercised without TPU pods.

These env vars must be set before jax initializes a backend, hence the
top-of-conftest placement.
"""

import os

# The suite is DEFINED on the simulated CPU mesh: pin the platform in the
# environment, so the subprocesses the tests spawn inherit it too (on a
# TPU host a child that reached for the chip would take it, or hang on
# one this process holds).
os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()
# Keep TF (used only as a model loader in ingest tests) off any accelerator
# and quiet.
os.environ.setdefault("CUDA_VISIBLE_DEVICES", "-1")
os.environ.setdefault("TF_CPP_MIN_LOG_LEVEL", "2")

import numpy as np  # noqa: E402
import pytest  # noqa: E402


@pytest.fixture(scope="session")
def rng():
    return np.random.default_rng(0)


@pytest.fixture(scope="session")
def mesh8():
    import jax

    from tpudl import mesh as M

    assert jax.device_count() >= 8, "conftest failed to fake 8 devices"
    return M.build_mesh(n_data=8)


@pytest.fixture(scope="session")
def mesh4x2():
    from tpudl import mesh as M

    return M.build_mesh(n_data=4, n_model=2)


def _count_eqns(jaxpr, primitive: str, but: str | None = None,
                named: str | None = None) -> int:
    """Equations of ``primitive`` in ``jaxpr`` and in every jaxpr its
    equations carry (``pjit``, ``checkpoint``, ``scan``, ``custom_vjp``
    bodies), each body once however often a loop runs it; with ``but``
    (a prefix or a tuple of them), those whose ``name`` parameter (a
    ``pallas_call``'s or a ``jit``'s) starts with it are left out, with
    ``named`` only those whose ``name`` is ``named`` count."""
    jaxpr = getattr(jaxpr, "jaxpr", jaxpr)
    n = 0
    for eqn in jaxpr.eqns:
        name = str(eqn.params.get("name"))
        n += (eqn.primitive.name == primitive
              and not (but and name.startswith(but))
              and named in (None, name))
        for value in eqn.params.values():
            for inner in value if isinstance(value, (list, tuple)) else [value]:
                inner = getattr(inner, "jaxpr", inner)
                if hasattr(inner, "eqns"):
                    n += _count_eqns(inner, primitive, but, named)
    return n


@pytest.fixture(scope="session")
def count_eqns():
    return _count_eqns


@pytest.fixture
def check_flash_saved_once(monkeypatch):
    """``check(lm, params, ids, bodies)`` for a decoder of any family
    whose stack TRACES ``bodies`` attention parts (a scanned run once):
    under ``loss_fn(remat=True)`` the gradient holds two kernels a
    body where the routes-only policy of PR 34 holds three, the loss and
    every gradient leaf are what that policy gives (to float32 rounding:
    the interpreted kernel is XLA operations, which a second compilation
    in another place contracts apart; on the chip a Mosaic kernel is one
    binary and the saved bits ARE the recomputed ones), and the counter
    and the gauge read what the shapes say."""
    import jax

    from tpudl import obs
    from tpudl.zoo import decoder, moe

    def step_of(lm):   # a new function a call: jit's cache is keyed by it
        return jax.value_and_grad(lm.loss_fn(remat=True))

    def check(lm, params, ids, bodies):
        def read():
            snap = obs.snapshot("zoo.lm.attention.")
            return {k: snap.get(f"zoo.lm.attention.{k}", {"value": 0})["value"]
                    for k in ("saved", "saved_bytes")}

        before = read()
        jaxpr = jax.make_jaxpr(step_of(lm))(params, ids)
        after, layers = read(), lm.kinds()["attention"]
        assert after["saved"] - before["saved"] == layers
        if layers:    # the gauge is of the last program that saved
            assert after["saved_bytes"] == layers * ids.size * lm.heads * (
                lm.v_head_dim * params["embed"].dtype.itemsize + 4)
        # the flash kernels; a mixer's scan kernels and the experts'
        # grouped products are named, and left out
        assert _count_eqns(jaxpr, "pallas_call", but=(
            "ssd_scan", "grouped_dot")) == 2 * bodies
        with jax.default_matmul_precision("highest"):
            got = jax.jit(step_of(lm))(params, ids)
        with monkeypatch.context() as m:
            m.setattr(decoder, "_SAVE_NAMED",
                      jax.checkpoint_policies.save_only_these_names(
                          moe.ROUTES))
            assert _count_eqns(jax.make_jaxpr(step_of(lm))(params, ids),
                               "pallas_call", but=(
                                   "ssd_scan", "grouped_dot")) == 3 * bodies
            with jax.default_matmul_precision("highest"):
                want = jax.jit(step_of(lm))(params, ids)
        assert abs(float(got[0]) - float(want[0])) < 1e-6
        assert sorted(got[1]) == sorted(want[1])
        for name, leaf in want[1].items():
            gap = np.linalg.norm(np.asarray(got[1][name]) - np.asarray(leaf))
            assert gap <= 1e-5 * np.linalg.norm(leaf), name

    return check
