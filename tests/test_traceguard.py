"""tpudl.analysis.traceguard + tpudl.testing.traceck: the jit-boundary
contract (ANALYSIS.md "Trace rules").

Four layers, mirroring tests/test_analysis.py and test_concurrency.py:

1. per-rule fixtures — every trace rule proven LIVE by a positive
   snippet, kept honest by a negative, silenced by a suppression
   (with the required reason);
2. THE seeded storm — one source produces a static ``jit-cache-churn``
   finding AND, run under ``TPUDL_TRACECK=1`` in a subprocess, a
   runtime recompile-storm finding that ``obs doctor`` classifies as
   ``recompile_storm`` — both halves fire from one cause;
3. the stale-suppression audit + SARIF emitter (the gate satellites);
4. acceptance — the repo's own tree is clean under the five trace
   rules + the stale audit, inside the 20 s analyzer budget.
"""

import gzip
import importlib.util
import json
import os
import subprocess
import sys
import textwrap
import time

import pytest

from tpudl.analysis import (RULES, TRACE_RULES, analyze_trace_sources,
                            traced_functions)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CHECK_TARGETS = [os.path.join(REPO, "tpudl"), os.path.join(REPO, "tools")]


def _load_cli():
    spec = importlib.util.spec_from_file_location(
        "tpudl_check", os.path.join(REPO, "tools", "tpudl_check.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def trace_findings(src: str, rule: str | None = None,
                   rel: str = "pkg/mod.py"):
    fs = analyze_trace_sources({rel: src})
    if rule is not None:
        fs = [f for f in fs if f.rule == rule]
    return fs


# ---------------------------------------------------------------------------
# the traced set (phase 1)
# ---------------------------------------------------------------------------

class TestTracedSet:
    def _traced(self, src: str, rel: str = "pkg/mod.py"):
        return traced_functions({rel: src})

    def test_jit_call_and_decorator_roots(self):
        src = (
            "import jax\n"
            "from functools import partial\n"
            "@jax.jit\n"
            "def a(x):\n"
            "    return x\n"
            "@partial(jax.jit, static_argnums=(1,))\n"
            "def b(x, n):\n"
            "    return x * n\n"
            "def c(x):\n"
            "    return x\n"
            "jfn = jax.jit(c)\n")
        traced = self._traced(src)
        quals = {k.split(":")[1] for k in traced}
        assert {"a", "b", "c"} <= quals
        bwhy = traced["pkg.mod:b"]
        assert bwhy.static_params == {"n"}

    def test_scan_fused_wrap_device_fn_roots(self):
        src = (
            "import jax\n"
            "from jax import lax\n"
            "def body(carry, x):\n"
            "    return carry, x\n"
            "def d(x):\n"
            "    return x\n"
            "def e(x):\n"
            "    return x\n"
            "def f(x):\n"
            "    return x\n"
            "def run(frame, plan, _fused_wrapper):\n"
            "    lax.scan(body, None, ())\n"
            "    _fused_wrapper(d, 4)\n"
            "    plan.wrap(e, donate=True)\n"
            "    frame.map_batches(f, device_fn=True)\n")
        traced = self._traced(src)
        quals = {k.split(":")[1] for k in traced}
        assert {"body", "d", "e", "f"} <= quals
        assert "run" not in quals

    def test_transitive_closure_marks_callees(self):
        src = (
            "import jax\n"
            "def helper(x):\n"
            "    return x + 1\n"
            "def step(x):\n"
            "    return helper(x)\n"
            "jfn = jax.jit(step)\n")
        traced = self._traced(src)
        quals = {k.split(":")[1] for k in traced}
        assert {"step", "helper"} <= quals
        assert traced["pkg.mod:helper"].via == "step"

    def test_external_module_attrs_never_resolve_by_bare_name(self):
        """`jnp.log` / `jax.lax.scan` must not mark some repo function
        named `log`/`scan` traced — the mismatch that would flood the
        sweep with phantom findings."""
        src = (
            "import jax\n"
            "import jax.numpy as jnp\n"
            "def log(msg):\n"
            "    print(msg)\n"
            "def step(x):\n"
            "    return jnp.log(x)\n"
            "jfn = jax.jit(step)\n")
        traced = self._traced(src)
        quals = {k.split(":")[1] for k in traced}
        assert "log" not in quals


# ---------------------------------------------------------------------------
# rule: trace-time-effect
# ---------------------------------------------------------------------------

class TestTraceTimeEffect:
    def test_counter_in_traced_fn_fires(self):
        src = (
            "import jax\n"
            "from tpudl.obs import metrics\n"
            "def step(x):\n"
            "    metrics.counter('train.steps').inc()\n"
            "    return x\n"
            "jfn = jax.jit(step)\n")
        fs = trace_findings(src, "trace-time-effect")
        assert len(fs) == 1 and fs[0].line == 4
        assert "counter" in fs[0].message

    def test_env_read_print_logging_fire(self):
        src = (
            "import jax\n"
            "import os\n"
            "import logging\n"
            "log = logging.getLogger('x')\n"
            "def step(x):\n"
            "    flag = os.environ.get('TPUDL_WIRE_CODEC')\n"
            "    print(flag)\n"
            "    log.warning('traced!')\n"
            "    return x\n"
            "jfn = jax.jit(step)\n")
        fs = trace_findings(src, "trace-time-effect")
        assert [f.line for f in fs] == [6, 7, 8]

    def test_effect_via_transitive_callee_fires_at_callee(self):
        src = (
            "import jax\n"
            "def breadcrumb(x):\n"
            "    print('hi')\n"
            "    return x\n"
            "def step(x):\n"
            "    return breadcrumb(x)\n"
            "jfn = jax.jit(step)\n")
        fs = trace_findings(src, "trace-time-effect")
        assert len(fs) == 1 and fs[0].line == 3

    def test_log_like_receivers_are_not_loggers(self):
        """catalog.error / dialog.warning are domain calls, not
        logging (review regression); real loggers still fire."""
        src = (
            "import jax\n"
            "def step(x, catalog, dialog):\n"
            "    catalog.error(x)\n"
            "    dialog.warning(x)\n"
            "    return x\n"
            "jfn = jax.jit(step)\n")
        assert trace_findings(src, "trace-time-effect") == []
        src2 = (
            "import jax\n"
            "def step(x, logger):\n"
            "    logger.error('per-step!')\n"
            "    return x\n"
            "jfn = jax.jit(step)\n")
        assert len(trace_findings(src2, "trace-time-effect")) == 1

    def test_effect_outside_traced_code_is_clean(self):
        src = (
            "import jax\n"
            "from tpudl.obs import metrics\n"
            "def step(x):\n"
            "    return x + 1\n"
            "def host_loop(xs):\n"
            "    jfn = jax.jit(step)\n"
            "    for x in xs:\n"
            "        metrics.counter('frame.map_batches.runs').inc()\n"
            "        jfn(x)\n")
        assert trace_findings(src, "trace-time-effect") == []

    def test_suppression_with_reason_silences(self):
        src = (
            "import jax\n"
            "def step(x):\n"
            "    # tpudl: ignore[trace-time-effect] — trace-time banner\n"
            "    # is deliberate: one line per compile, not per step\n"
            "    print('compiling')\n"
            "    return x\n"
            "jfn = jax.jit(step)\n")
        assert trace_findings(src, "trace-time-effect") == []

    def test_suppression_on_def_line_covers_the_fn(self):
        src = (
            "import jax\n"
            "# tpudl: ignore[trace-time-effect] — debug build only\n"
            "def step(x):\n"
            "    print('compiling')\n"
            "    return x\n"
            "jfn = jax.jit(step)\n")
        assert trace_findings(src, "trace-time-effect") == []


# ---------------------------------------------------------------------------
# rule: host-op-on-traced
# ---------------------------------------------------------------------------

class TestHostOpOnTraced:
    def test_np_call_on_traced_value_fires(self):
        src = (
            "import jax\n"
            "import numpy as np\n"
            "def step(x):\n"
            "    return np.asarray(x) + 1\n"
            "jfn = jax.jit(step)\n")
        fs = trace_findings(src, "host-op-on-traced")
        assert len(fs) == 1 and fs[0].line == 4
        assert "np.asarray" in fs[0].message

    def test_item_and_float_coercions_fire(self):
        src = (
            "import jax\n"
            "def step(x):\n"
            "    a = x.sum().item()\n"
            "    b = float(x)\n"
            "    return a + b\n"
            "jfn = jax.jit(step)\n")
        assert [f.line for f in
                trace_findings(src, "host-op-on-traced")] == [3, 4]

    def test_np_on_static_shape_is_clean(self):
        """np.* over static-under-trace info (shapes, fresh constants)
        is the legitimate constant-building idiom."""
        src = (
            "import jax\n"
            "import numpy as np\n"
            "def step(x):\n"
            "    mask = np.zeros(x.shape)\n"
            "    return x + mask\n"
            "jfn = jax.jit(step)\n")
        assert trace_findings(src, "host-op-on-traced") == []

    def test_static_param_coercion_is_clean(self):
        src = (
            "import jax\n"
            "from functools import partial\n"
            "@partial(jax.jit, static_argnums=(1,))\n"
            "def step(x, n):\n"
            "    return x * int(n)\n")
        assert trace_findings(src, "host-op-on-traced") == []

    def test_suppression_with_reason_silences(self):
        src = (
            "import jax\n"
            "import numpy as np\n"
            "def step(x):\n"
            "    # tpudl: ignore[host-op-on-traced] — x is a host-side\n"
            "    # shim input here, never an abstract tracer\n"
            "    return np.asarray(x) + 1\n"
            "jfn = jax.jit(step)\n")
        assert trace_findings(src, "host-op-on-traced") == []


# ---------------------------------------------------------------------------
# rule: traced-branch
# ---------------------------------------------------------------------------

class TestTracedBranch:
    def test_if_on_traced_value_fires(self):
        src = (
            "import jax\n"
            "def step(x):\n"
            "    if x > 0:\n"
            "        return x\n"
            "    return -x\n"
            "jfn = jax.jit(step)\n")
        fs = trace_findings(src, "traced-branch")
        assert len(fs) == 1 and fs[0].line == 3

    def test_deep_assignment_chain_still_traced(self):
        """Dataflow runs to a fixpoint — a depth-4 chain out of a
        jnp call must not escape the rule (review regression)."""
        src = (
            "import jax\n"
            "import jax.numpy as jnp\n"
            "def step(y):\n"
            "    a0 = jnp.log(y)\n"
            "    a1 = a0 + 1\n"
            "    a2 = a1 * 2\n"
            "    a3 = a2 - 1\n"
            "    if a3 > 0:\n"
            "        return a3\n"
            "    return y\n"
            "jfn = jax.jit(step)\n")
        fs = trace_findings(src, "traced-branch")
        assert len(fs) == 1 and fs[0].line == 8

    def test_while_on_derived_traced_value_fires(self):
        src = (
            "import jax\n"
            "import jax.numpy as jnp\n"
            "def step(x):\n"
            "    s = jnp.sum(x)\n"
            "    while s > 0:\n"
            "        s = s - 1\n"
            "    return s\n"
            "jfn = jax.jit(step)\n")
        fs = trace_findings(src, "traced-branch")
        assert len(fs) == 1 and fs[0].line == 5

    def test_shape_dispatch_is_clean(self):
        """Branching on .shape/.ndim/len()/is-None is static under
        trace — the house idiom, never flagged."""
        src = (
            "import jax\n"
            "def step(x, y):\n"
            "    if x.ndim == 3:\n"
            "        x = x[None]\n"
            "    if y is None:\n"
            "        return x\n"
            "    if len(x.shape) > 2 and isinstance(y, tuple):\n"
            "        return x\n"
            "    return x + 1\n"
            "jfn = jax.jit(step)\n")
        assert trace_findings(src, "traced-branch") == []

    def test_static_argnum_branch_is_clean(self):
        src = (
            "import jax\n"
            "from functools import partial\n"
            "@partial(jax.jit, static_argnames=('causal',))\n"
            "def step(x, causal):\n"
            "    if causal:\n"
            "        return x\n"
            "    return -x\n")
        assert trace_findings(src, "traced-branch") == []

    def test_suppression_with_reason_silences(self):
        src = (
            "import jax\n"
            "def step(x):\n"
            "    # tpudl: ignore[traced-branch] — x is weak-typed\n"
            "    # concrete at every call site (documented contract)\n"
            "    if x > 0:\n"
            "        return x\n"
            "    return -x\n"
            "jfn = jax.jit(step)\n")
        assert trace_findings(src, "traced-branch") == []


# ---------------------------------------------------------------------------
# rule: donation-reuse
# ---------------------------------------------------------------------------

class TestDonationReuse:
    def test_reuse_after_donating_call_fires(self):
        src = (
            "import jax\n"
            "def run(fn, buf):\n"
            "    g = jax.jit(fn, donate_argnums=(0,))\n"
            "    out = g(buf)\n"
            "    return buf.sum() + out\n")
        fs = trace_findings(src, "donation-reuse")
        assert len(fs) == 1 and fs[0].line == 5
        assert "buf" in fs[0].message

    def test_house_wrapper_donate_kwarg_fires(self):
        src = (
            "def run(plan, fn, batch):\n"
            "    g = plan.wrap(fn, donate=True)\n"
            "    out = g(batch)\n"
            "    size = batch.nbytes\n"
            "    return out, size\n")
        fs = trace_findings(src, "donation-reuse")
        assert len(fs) == 1 and fs[0].line == 4

    def test_donate_and_rebind_idiom_is_clean(self):
        """`params = step(params)` — the canonical JAX donation
        pattern: the call line rebinds the name to the RESULT, so
        later reads never touch the donated buffer (review
        regression)."""
        src = (
            "import jax\n"
            "def run(fn, x):\n"
            "    g = jax.jit(fn, donate_argnums=(0,))\n"
            "    x = g(x)\n"
            "    return x + 1\n")
        assert trace_findings(src, "donation-reuse") == []

    def test_empty_donate_argnums_is_clean(self):
        """donate_argnums=() is an explicit donate-NOTHING — it must
        not invert into donate-everything (review regression)."""
        src = (
            "import jax\n"
            "def run(fn, buf):\n"
            "    g = jax.jit(fn, donate_argnums=())\n"
            "    out = g(buf)\n"
            "    return buf.sum() + out\n")
        assert trace_findings(src, "donation-reuse") == []

    def test_donate_argnums_zero_is_a_position_not_a_flag(self):
        src = (
            "import jax\n"
            "def run(fn, buf):\n"
            "    g = jax.jit(fn, donate_argnums=0)\n"
            "    out = g(buf)\n"
            "    return buf.sum() + out\n")
        fs = trace_findings(src, "donation-reuse")
        assert len(fs) == 1 and fs[0].line == 5

    def test_nondonated_position_is_clean(self):
        src = (
            "import jax\n"
            "def run(fn, a, b):\n"
            "    g = jax.jit(fn, donate_argnums=(0,))\n"
            "    out = g(a, b)\n"
            "    return b.sum() + out\n")
        assert trace_findings(src, "donation-reuse") == []

    def test_rebind_before_reuse_is_clean(self):
        src = (
            "import jax\n"
            "def run(fn, buf, fresh):\n"
            "    g = jax.jit(fn, donate_argnums=(0,))\n"
            "    out = g(buf)\n"
            "    buf = fresh()\n"
            "    return buf.sum() + out\n")
        assert trace_findings(src, "donation-reuse") == []

    def test_loop_rebind_is_clean(self):
        """`for b in batches: out = g(b)` — each iteration's b is a
        fresh binding, not the donated buffer (the trailing read is
        metadata, which survives donation)."""
        src = (
            "import jax\n"
            "def run(fn, batches):\n"
            "    g = jax.jit(fn, donate_argnums=(0,))\n"
            "    outs = []\n"
            "    for b in batches:\n"
            "        outs.append(g(b))\n"
            "        n = b.shape[0]\n"
            "    return outs, n\n")
        assert trace_findings(src, "donation-reuse") == []

    def test_same_iteration_reuse_in_loop_fires(self):
        """A DATA read after the donating call in the same loop body
        executes before the next iteration's rebind — the rule's most
        common target shape must not hide behind the loop (review
        regression)."""
        src = (
            "import jax\n"
            "def run(fn, batches):\n"
            "    g = jax.jit(fn, donate_argnums=(0,))\n"
            "    total = 0.0\n"
            "    for b in batches:\n"
            "        y = g(b)\n"
            "        total = total + float(b.sum())\n"
            "    return total\n")
        fs = trace_findings(src, "donation-reuse")
        assert len(fs) == 1 and fs[0].line == 7

    def test_multiline_donating_call_args_are_not_reuse(self):
        """Black-style wrapped call args load the donated name on the
        call's CONTINUATION lines — that load IS the donation (review
        regression)."""
        src = (
            "import jax\n"
            "def run(fn, x):\n"
            "    g = jax.jit(fn, donate_argnums=(0,))\n"
            "    y = g(\n"
            "        x,\n"
            "    )\n"
            "    return y\n")
        assert trace_findings(src, "donation-reuse") == []

    def test_read_modify_write_after_donation_fires(self):
        """`x = x + 1` after donating x reads the dead buffer BEFORE
        the rebind lands — the classic bug must not hide behind its
        own store (review regression)."""
        src = (
            "import jax\n"
            "def run(fn, x):\n"
            "    g = jax.jit(fn, donate_argnums=(0,))\n"
            "    y = g(x)\n"
            "    x = x + 1\n"
            "    return y, x\n")
        fs = trace_findings(src, "donation-reuse")
        assert len(fs) == 1 and fs[0].line == 5

    def test_augmented_assignment_reads_the_donated_buffer(self):
        """`x += 1` reads the pre-assignment value even though the
        target ctx is Store — semantically identical to `x = x + 1`
        (review regression)."""
        src = (
            "import jax\n"
            "def run(fn, x):\n"
            "    g = jax.jit(fn, donate_argnums=(0,))\n"
            "    y = g(x)\n"
            "    x += 1\n"
            "    return y, x\n")
        fs = trace_findings(src, "donation-reuse")
        assert len(fs) == 1 and fs[0].line == 5

    def test_annotated_maker_binding_is_recognized(self):
        """`g: Callable = jax.jit(f, donate_argnums=...)` — an
        annotation must not hide the donating maker (review
        regression)."""
        src = (
            "import jax\n"
            "def run(fn, buf):\n"
            "    g: object = jax.jit(fn, donate_argnums=(0,))\n"
            "    out = g(buf)\n"
            "    return buf.sum() + out\n")
        fs = trace_findings(src, "donation-reuse")
        assert len(fs) == 1 and fs[0].line == 5

    def test_metadata_read_after_donation_is_clean(self):
        """Reading .shape/.ndim/len() of a donated array is legal —
        only DATA access dies (review regression)."""
        src = (
            "import jax\n"
            "def run(fn, x):\n"
            "    g = jax.jit(fn, donate_argnums=(0,))\n"
            "    y = g(x)\n"
            "    return y.reshape(x.shape), len(x), x.ndim\n")
        assert trace_findings(src, "donation-reuse") == []

    def test_suppression_with_reason_silences(self):
        src = (
            "import jax\n"
            "def run(fn, buf):\n"
            "    g = jax.jit(fn, donate_argnums=(0,))\n"
            "    out = g(buf)\n"
            "    # tpudl: ignore[donation-reuse] — u8 wire batch can\n"
            "    # never alias the f32 output; donation is ignored\n"
            "    return buf.sum() + out\n")
        assert trace_findings(src, "donation-reuse") == []


# ---------------------------------------------------------------------------
# rule: jit-cache-churn
# ---------------------------------------------------------------------------

class TestJitCacheChurn:
    def test_jit_in_loop_fires(self):
        src = (
            "import jax\n"
            "def run(xs):\n"
            "    outs = []\n"
            "    for x in xs:\n"
            "        fn = jax.jit(lambda v: v + 1)\n"
            "        outs.append(fn(x))\n"
            "    return outs\n")
        fs = trace_findings(src, "jit-cache-churn")
        assert len(fs) == 1 and fs[0].line == 5
        assert "loop" in fs[0].message

    def test_per_call_closure_fires(self):
        src = (
            "import jax\n"
            "def run(x):\n"
            "    fn = jax.jit(lambda v: v + 1)\n"
            "    return fn(x)\n")
        fs = trace_findings(src, "jit-cache-churn")
        assert len(fs) == 1 and "closure" in fs[0].message

    def test_unhashable_static_arg_fires(self):
        src = (
            "import jax\n"
            "def run(h, x):\n"
            "    g = jax.jit(h, static_argnums=(1,))\n"
            "    return g(x, [2, 3])\n")
        fs = trace_findings(src, "jit-cache-churn")
        assert len(fs) == 1 and "unhashable" in fs[0].message

    def test_factory_return_is_clean(self):
        """make_train_step's shape: the jit result ESCAPES to the
        caller, who owns retention — not churn."""
        src = (
            "import jax\n"
            "def make_step(loss):\n"
            "    def step(params, batch):\n"
            "        return loss(params, batch)\n"
            "    return jax.jit(step, donate_argnums=(0,))\n")
        assert trace_findings(src, "jit-cache-churn") == []

    def test_annotated_factory_return_is_clean(self):
        """`g: object = jax.jit(local); return g` — the annotation
        must not defeat the caller-owned-retention exemption (review
        regression)."""
        src = (
            "import jax\n"
            "def make():\n"
            "    def local(a):\n"
            "        return a + 1\n"
            "    g: object = jax.jit(local)\n"
            "    return g\n")
        assert trace_findings(src, "jit-cache-churn") == []

    def test_subscript_cached_jit_in_loop_is_clean(self):
        src = (
            "import jax\n"
            "def run(cache, keys, x):\n"
            "    for k in keys:\n"
            "        cache[k] = jax.jit(lambda v: v + 1)\n"
            "    return cache[keys[0]](x)\n")
        assert trace_findings(src, "jit-cache-churn") == []

    def test_lru_cached_factory_is_clean(self):
        src = (
            "import functools\n"
            "import jax\n"
            "@functools.lru_cache(maxsize=1)\n"
            "def identity_jit():\n"
            "    return jax.jit(lambda t: t)\n")
        assert trace_findings(src, "jit-cache-churn") == []

    def test_house_wrapper_with_stable_fn_in_loop_is_clean(self):
        """_fused_wrapper retains on fn identity — calling it per
        batch over a STABLE fn is the pattern working."""
        src = (
            "def run(_fused_wrapper, fn, batches):\n"
            "    outs = []\n"
            "    for b in batches:\n"
            "        g = _fused_wrapper(fn, 4)\n"
            "        outs.append(g(b))\n"
            "    return outs\n")
        assert trace_findings(src, "jit-cache-churn") == []

    def test_house_wrapper_with_fresh_lambda_fires(self):
        src = (
            "def run(_fused_wrapper, b):\n"
            "    g = _fused_wrapper(lambda v: v + 1, 4)\n"
            "    return g(b)\n")
        fs = trace_findings(src, "jit-cache-churn")
        assert len(fs) == 1 and "per-call fn identity" in fs[0].message

    def test_single_line_loop_body_jit_fires(self):
        """`for f in fs: outs.append(jax.jit(f))` — the call shares
        the loop header's line; formatting must not hide a real
        per-iteration retrace (review regression)."""
        src = (
            "import jax\n"
            "def run(fs, outs):\n"
            "    for f in fs: outs.append(jax.jit(f)(1.0))\n")
        fs = trace_findings(src, "jit-cache-churn")
        assert len(fs) == 1 and "loop" in fs[0].message

    def test_module_level_jit_of_module_def_is_clean(self):
        """`jfn = jax.jit(helper)` at module scope is the canonical
        hoist the rule's own hint prescribes — one trace per process
        (review regression)."""
        src = (
            "import jax\n"
            "def helper(x):\n"
            "    return x + 1\n"
            "jfn = jax.jit(helper)\n"
            "gfn = jax.jit(lambda v: v * 2)\n")
        assert trace_findings(src, "jit-cache-churn") == []

    def test_module_level_jit_in_loop_fires(self):
        """A script-level warmup loop is the canonical churn pattern;
        the doctor's remediation pointer (run the static rule) must
        not dead-end on it (review regression)."""
        src = (
            "import jax\n"
            "for i in range(10):\n"
            "    fn = jax.jit(lambda x: x + i)\n"
            "    fn(1.0)\n")
        fs = trace_findings(src, "jit-cache-churn")
        assert len(fs) == 1 and fs[0].line == 3
        assert "loop" in fs[0].message

    def test_suppression_with_reason_silences(self):
        src = (
            "import jax\n"
            "def run(x):\n"
            "    # tpudl: ignore[jit-cache-churn] — one-shot probe\n"
            "    # program; runs once per process by construction\n"
            "    fn = jax.jit(lambda v: v + 1)\n"
            "    return fn(x)\n")
        assert trace_findings(src, "jit-cache-churn") == []


# ---------------------------------------------------------------------------
# THE seeded storm: both halves from one source
# ---------------------------------------------------------------------------

STORM_SRC = textwrap.dedent("""\
    import jax
    import jax.numpy as jnp


    def churn(n):
        x = jnp.ones((4,))
        outs = []
        for i in range(n):
            fn = jax.jit(lambda v: v + 1.0)
            outs.append(fn(x))
        return outs
""")


class TestSeededStorm:
    def test_static_half_flags_the_churn(self):
        fs = trace_findings(STORM_SRC, "jit-cache-churn",
                            rel="pkg/storm.py")
        assert len(fs) == 1
        assert fs[0].line == 9

    @pytest.mark.slow
    def test_runtime_half_storms_and_doctor_classifies(self, tmp_path):
        """One subprocess, TPUDL_TRACECK=1: the same source retraces
        past the threshold, the sentinel files the storm into the
        flight ring + traceck.* counters, and obs doctor classifies
        the dump as recompile_storm."""
        storm_py = tmp_path / "storm_src.py"
        storm_py.write_text(STORM_SRC)
        driver = tmp_path / "driver.py"
        driver.write_text(textwrap.dedent(f"""\
            import sys
            sys.path.insert(0, {str(REPO)!r})
            sys.path.insert(0, {str(tmp_path)!r})
            import tpudl  # arms traceck from TPUDL_TRACECK=1
            from tpudl.testing import traceck
            assert traceck.installed()
            import storm_src
            storm_src.churn(6)
            assert traceck.findings(), "no storm filed"
            from tpudl.obs import flight
            flight.dump(reason="manual")
        """))
        env = dict(os.environ, TPUDL_TRACECK="1", TPUDL_TRACECK_STORM="3",
                   TPUDL_FLIGHT_DIR=str(tmp_path), JAX_PLATFORMS="cpu")
        proc = subprocess.run([sys.executable, str(driver)],
                              capture_output=True, text=True, env=env,
                              timeout=300)
        assert proc.returncode == 0, proc.stderr[-2000:]
        from tpudl.obs import doctor
        dumps = doctor.load_dumps(str(tmp_path))
        assert dumps, "no flight dump written"
        merged = doctor.merge_dumps(dumps)
        diag = doctor.classify(merged)
        assert diag["classification"] == "recompile_storm"
        assert diag["suspect_stage"] == "dispatch"
        assert any("storm_src" in e or "recompile" in e
                   for e in diag["evidence"])
        # the dump's metrics carry the counters
        host = list(merged["hosts"].values())[0]
        assert host["metrics"]["traceck.storms"]["value"] >= 1
        assert host["metrics"]["traceck.retraces"]["value"] >= 3

    def test_doctor_rule_order_storm_beats_stall_loses_to_preempt(self):
        from tpudl.obs import doctor

        def dump_with(metrics=None, events=None, stalls=None):
            return {"hosts": {"0": {"ts": 1.0, "reason": "exception",
                                    "metrics": metrics or {},
                                    "events": events or []}},
                    "stalls": stalls or [], "errors": [],
                    "restarts": [], "spans": []}
        storm_m = {"traceck.storms": {"value": 1.0},
                   "traceck.retraces": {"value": 5.0}}
        # storm + stall → the storm explains the stall
        d = dump_with(metrics=storm_m,
                      stalls=[{"name": "frame", "age_s": 9.0,
                               "info": {"stage": "dispatch"}}])
        assert doctor.classify(d)["classification"] == "recompile_storm"
        # preempted-resumable still wins over everything
        d = dump_with(metrics=storm_m,
                      events=[{"kind": "job.preempted",
                               "manifest": "m.json"}])
        assert doctor.classify(d)["classification"] == \
            "preempted_resumable"

    def test_rejit_of_stable_fn_is_one_trace_not_a_storm(self):
        """`jax.jit(f)(x)` repeated over a STABLE f is one trace
        unarmed — the shim must be memoized per fn object so the
        sentinel never manufactures the retraces it reports (review
        regression)."""
        import jax
        import jax.numpy as jnp
        from tpudl.testing import traceck

        def stable(v):
            return v * 2.0

        traceck.reset()
        traceck.arm()
        try:
            x = jnp.ones((2,))
            for _ in range(6):
                jax.jit(stable)(x)
            assert traceck.findings() == []
            assert sum(traceck.counts().values()) == 1
        finally:
            traceck.disarm()
            traceck.uninstall()
            traceck.reset()

    def test_disable_jit_eager_reexecution_is_not_a_trace(self):
        """Under jax.disable_jit() the body re-runs eagerly per call —
        counting those would file false storms (review regression)."""
        import jax
        import jax.numpy as jnp
        from tpudl.testing import traceck
        traceck.reset()
        traceck.arm()
        try:
            g = jax.jit(lambda v: v * 2.0)
            with jax.disable_jit():
                for _ in range(6):
                    g(jnp.ones((2,)))
            assert traceck.findings() == []
            assert sum(traceck.counts().values()) == 0
        finally:
            traceck.disarm()
            traceck.uninstall()
            traceck.reset()

    def test_traceck_unarmed_by_default_in_this_process(self):
        from tpudl.testing import traceck
        assert traceck.enabled() is False

    def test_traceck_arm_counts_and_uninstalls_cleanly(self):
        import jax
        import jax.numpy as jnp
        from tpudl.testing import traceck
        real_jit = jax.jit
        traceck.reset()
        traceck.arm()
        try:
            assert traceck.installed()
            x = jnp.ones((2,))
            for _ in range(2):
                jax.jit(lambda v: v * 2.0)(x)
            counts = traceck.counts()
            assert sum(counts.values()) >= 2
            # fresh lambdas collapse onto ONE code-location identity
            assert max(counts.values()) >= 2
            # a module that bound `jit = jax.jit` while armed must
            # keep a WORKING jit after uninstall (review regression:
            # the shim closes over the real jit, not the module
            # global uninstall clears)
            bound_while_armed = jax.jit
        finally:
            traceck.disarm()
            traceck.uninstall()
            traceck.reset()
        assert jax.jit is real_jit
        out = bound_while_armed(lambda v: v + 1.0)(jnp.ones((2,)))
        assert float(out.sum()) == 4.0


# ---------------------------------------------------------------------------
# satellite: stale-suppression audit
# ---------------------------------------------------------------------------

class TestStaleSuppression:
    def _gate(self, tmp_path, src, name="mod.py", **kw):
        cli = _load_cli()
        p = tmp_path / name
        p.write_text(src)
        return cli.collect_findings([str(p)], root=str(tmp_path), **kw)

    def test_stale_ignore_is_reported(self, tmp_path):
        src = (
            "def fine():\n"
            "    # tpudl: ignore[hot-sync] — was hot before the\n"
            "    # executor rework\n"
            "    return 1\n")
        findings, errors = self._gate(tmp_path, src)
        assert errors == []
        stale = [f for f in findings if f.rule == "stale-suppression"]
        assert len(stale) == 1 and stale[0].line == 2
        assert "hot-sync" in stale[0].message

    def test_live_ignore_is_not_reported(self, tmp_path):
        src = (
            "import time\n"
            "def f(g):\n"
            "    while True:\n"
            "        try:\n"
            "            return g()\n"
            "        except ValueError as e:\n"
            "            print(e)\n"
            "            # tpudl: ignore[adhoc-retry] — test-only\n"
            "            # pacing loop, counted by the caller\n"
            "            time.sleep(0.1)\n")
        findings, _ = self._gate(tmp_path, src)
        assert [f for f in findings
                if f.rule == "stale-suppression"] == []
        assert [f for f in findings if f.rule == "adhoc-retry"] == []

    def test_allow_stale_in_exempts_fixture_trees(self, tmp_path):
        src = (
            "def fine():\n"
            "    # tpudl: ignore[hot-sync] — fixture: deliberately\n"
            "    # stale for the audit's own tests\n"
            "    return 1\n")
        findings, _ = self._gate(tmp_path, src,
                                 allow_stale_in=("fixtures",))
        assert [f for f in findings
                if f.rule == "stale-suppression"], \
            "non-matching prefix must not exempt"
        fixdir = tmp_path / "fixtures"
        fixdir.mkdir()
        cli = _load_cli()
        (fixdir / "mod.py").write_text(src)
        findings, _ = cli.collect_findings(
            [str(fixdir / "mod.py")], root=str(tmp_path),
            allow_stale_in=("fixtures",))
        assert [f for f in findings
                if f.rule == "stale-suppression"] == []

    def test_allow_stale_in_is_segment_aware(self, tmp_path):
        """tests/fixtures must not exempt tests/fixtures_extra/
        (review regression)."""
        src = (
            "def fine():\n"
            "    # tpudl: ignore[hot-sync] — rotted\n"
            "    return 1\n")
        sib = tmp_path / "fixtures_extra"
        sib.mkdir()
        (sib / "mod.py").write_text(src)
        cli = _load_cli()
        findings, _ = cli.collect_findings(
            [str(sib / "mod.py")], root=str(tmp_path),
            allow_stale_in=(str(tmp_path / "fixtures"),))
        assert [f for f in findings
                if f.rule == "stale-suppression"], \
            "sibling prefix must not be exempted"
        findings, _ = cli.collect_findings(
            [str(sib / "mod.py")], root=str(tmp_path),
            allow_stale_in=(str(sib),))
        assert [f for f in findings
                if f.rule == "stale-suppression"] == []

    def test_keeper_ignore_keeps_a_deliberately_stale_one(self, tmp_path):
        src = (
            "def fine():\n"
            "    # tpudl: ignore[hot-sync, stale-suppression] — kept\n"
            "    # as documentation of the old hot path\n"
            "    return 1\n")
        findings, _ = self._gate(tmp_path, src)
        assert [f for f in findings
                if f.rule == "stale-suppression"] == []

    def test_rules_filter_without_stale_skips_the_audit(self, tmp_path):
        src = (
            "def fine():\n"
            "    # tpudl: ignore[lock-order] — looks stale, but a\n"
            "    # hot-sync-only run cannot judge a concurrency rule\n"
            "    return 1\n")
        findings, _ = self._gate(tmp_path, src, rules={"hot-sync"})
        assert findings == []

    def test_concurrency_suppression_used_marks_cross_half(self, tmp_path):
        """A suppression absorbed by the INTERPROCEDURAL half must not
        be stale in the per-file half's eyes — usage merges."""
        src = (
            "import threading\n"
            "import time\n"
            "_lk = threading.Lock()\n"
            "def slow():\n"
            "    with _lk:\n"
            "        # tpudl: ignore[lock-held-blocking] — the sleep\n"
            "        # IS the paced critical section under test\n"
            "        time.sleep(0.01)\n")
        findings, _ = self._gate(tmp_path, src)
        assert [f for f in findings
                if f.rule == "stale-suppression"] == []
        assert [f for f in findings
                if f.rule == "lock-held-blocking"] == []

    def test_subtree_run_never_judges_graph_rule_suppressions(self):
        """`tpudl_check tpudl/testing` truncates the call graph — a
        legitimate concurrency/trace suppression whose evidence lives
        outside the subtree must not read as rot (review regression).
        The full gate (top-level trees) still judges everything."""
        cli = _load_cli()
        findings, errors = cli.collect_findings(
            [os.path.join(REPO, "tpudl", "testing")], root=REPO)
        assert errors == []
        stale = [f for f in findings if f.rule == "stale-suppression"]
        assert stale == [], "\n".join(f.render() for f in stale)

    def test_standalone_file_scan_never_judges_graph_rules(self,
                                                           tmp_path):
        """`tpudl_check chip_smoke.py` alone carries no package graph —
        a root script's graph-rule suppression (seeded into a copy: the
        script itself carries none) must not read as rot without the
        tpudl/ tree in the scan, and does once a directory root is
        scanned (review regression)."""
        cli = _load_cli()
        script = tmp_path / "chip_smoke.py"
        with open(os.path.join(REPO, "chip_smoke.py")) as f:
            script.write_text(
                f.read()
                + "\n# tpudl: ignore[signal-lock] — seeded by the test\n"
                + "_SEEDED = 1\n")

        def stale(paths):
            findings, errors = cli.collect_findings(
                paths, root=str(tmp_path), rules=["stale-suppression",
                                                  "signal-lock"])
            assert errors == []
            return [f for f in findings if f.rule == "stale-suppression"]

        assert stale([str(script)]) == []
        assert len(stale([str(tmp_path)])) == 1

    def test_graph_scope_is_cwd_independent(self):
        """The canonical gate invoked with ABSOLUTE paths from a
        foreign cwd must audit graph-rule suppressions exactly like
        the in-repo relative invocation (review regression)."""
        env = dict(os.environ, PYTHONPATH=REPO)
        r = subprocess.run(
            [sys.executable, os.path.join(REPO, "tools",
                                          "tpudl_check.py"),
             os.path.join(REPO, "tpudl"), os.path.join(REPO, "tools")],
            capture_output=True, text=True, env=env, timeout=300,
            cwd="/tmp")
        # clean gate — and graph-rule suppressions WERE judged: seed a
        # stale one in a copy to prove the audit was armed
        assert r.returncode == 0, r.stderr[-2000:]
        cli = _load_cli()
        supp = {"x.py": {2: [__import__("tpudl.analysis",
                                        fromlist=["Suppression"])
                            .Suppression(rules={"lock-order"},
                                         reason="r", line=2)]}}
        stale = cli._stale_findings((supp,), root=REPO,
                                    graph_scope=True)
        assert len(stale) == 1   # judged when graph_scope is True

    def test_keeper_of_skipped_graph_rule_not_judged_on_subtree(
            self, tmp_path):
        """A keeper guarding a graph-rule suppression that the
        truncated-graph scan skipped cannot be judged 'kept nothing'
        (review regression)."""
        src = (
            "def fine():\n"
            "    # tpudl: ignore[lock-order, stale-suppression] — kept\n"
            "    # as a deliberately-stale worked example\n"
            "    return 1\n")
        findings, _ = self._gate(tmp_path, src)  # file-only scan:
        # graph_scope is False, so neither the lock-order mark nor its
        # keeper may be judged
        assert [f for f in findings
                if f.rule == "stale-suppression"] == []

    def test_cli_exit_codes(self, tmp_path):
        p = tmp_path / "mod.py"
        p.write_text(
            "def fine():\n"
            "    # tpudl: ignore[hot-sync] — rotted\n"
            "    return 1\n")
        env = dict(os.environ, PYTHONPATH=REPO)
        r = subprocess.run(
            [sys.executable, os.path.join(REPO, "tools",
                                          "tpudl_check.py"), str(p)],
            capture_output=True, text=True, env=env, timeout=300)
        assert r.returncode == 2, r.stderr
        assert "stale-suppression" in r.stderr
        r = subprocess.run(
            [sys.executable, os.path.join(REPO, "tools",
                                          "tpudl_check.py"),
             "--allow-stale-in", str(tmp_path), str(p)],
            capture_output=True, text=True, env=env, timeout=300)
        assert r.returncode == 0, r.stderr


# ---------------------------------------------------------------------------
# satellite: SARIF 2.1.0 emitter
# ---------------------------------------------------------------------------

class TestSarif:
    def test_sarif_shape_contract(self, tmp_path):
        cli = _load_cli()
        p = tmp_path / "mod.py"
        p.write_text(
            "import jax\n"
            "def run(x):\n"
            "    fn = jax.jit(lambda v: v + 1)\n"
            "    return fn(x)\n")
        findings, errors = cli.collect_findings([str(p)],
                                                root=str(tmp_path))
        assert findings
        doc = cli.to_sarif(findings, errors)
        assert doc["version"] == "2.1.0"
        assert "sarif-schema-2.1.0" in doc["$schema"]
        assert len(doc["runs"]) == 1
        run = doc["runs"][0]
        driver = run["tool"]["driver"]
        assert driver["name"] == "tpudl-check"
        rule_ids = {r["id"] for r in driver["rules"]}
        assert set(RULES) <= rule_ids
        assert all(r["shortDescription"]["text"]
                   for r in driver["rules"])
        assert run["results"], "findings must map to results"
        res = run["results"][0]
        assert res["ruleId"] in rule_ids
        assert res["level"] == "warning"
        assert res["message"]["text"]
        loc = res["locations"][0]["physicalLocation"]
        assert loc["artifactLocation"]["uri"]
        assert loc["region"]["startLine"] >= 1
        assert loc["region"]["startColumn"] >= 1
        assert run["invocations"][0]["executionSuccessful"] is True

    def test_sarif_cli_flag_writes_file(self, tmp_path):
        p = tmp_path / "mod.py"
        p.write_text("def fine():\n    return 1\n")
        out = tmp_path / "gate.sarif"
        env = dict(os.environ, PYTHONPATH=REPO)
        r = subprocess.run(
            [sys.executable, os.path.join(REPO, "tools",
                                          "tpudl_check.py"),
             "--sarif", str(out), str(p)],
            capture_output=True, text=True, env=env, timeout=300)
        assert r.returncode == 0, r.stderr
        doc = json.loads(out.read_text())
        assert doc["version"] == "2.1.0"
        assert doc["runs"][0]["results"] == []

    def test_sarif_flag_needs_a_path(self):
        env = dict(os.environ, PYTHONPATH=REPO)
        r = subprocess.run(
            [sys.executable, os.path.join(REPO, "tools",
                                          "tpudl_check.py"), "--sarif"],
            capture_output=True, text=True, env=env, timeout=300)
        assert r.returncode == 1


# ---------------------------------------------------------------------------
# acceptance: the sweep is clean, inside budget
# ---------------------------------------------------------------------------

class TestAcceptance:
    def test_repo_clean_under_trace_rules_and_stale_audit(self):
        cli = _load_cli()
        t0 = time.perf_counter()
        findings, errors = cli.collect_findings(CHECK_TARGETS, root=REPO)
        dt = time.perf_counter() - t0
        assert errors == []
        offenders = [f for f in findings
                     if f.rule in TRACE_RULES
                     or f.rule == "stale-suppression"]
        assert offenders == [], "\n".join(
            f.render() for f in offenders[:20])
        # the <20 s analyzer budget guard covers ALL THREE halves +
        # the stale audit (the gate runs ahead of pytest in
        # run-tests.sh)
        assert dt < 20.0, f"analyzer took {dt:.1f}s"

    def test_analyze_reports_parse_errors(self, tmp_path):
        """An unparseable file is an ERROR, never a silent clean —
        the check_paths contract (review regression)."""
        from tpudl.analysis import analyze_trace
        (tmp_path / "bad.py").write_text("def broken(:\n")
        findings, errors = analyze_trace([str(tmp_path)],
                                         root=str(tmp_path))
        assert errors and "bad.py" in errors[0]

    def test_trace_rules_selectable_via_cli_rules_flag(self):
        env = dict(os.environ, PYTHONPATH=REPO)
        r = subprocess.run(
            [sys.executable, os.path.join(REPO, "tools",
                                          "tpudl_check.py"),
             "--rules", "jit-cache-churn,trace-time-effect",
             os.path.join(REPO, "tpudl", "analysis")],
            capture_output=True, text=True, env=env, timeout=300,
            cwd=REPO)
        assert r.returncode == 0, (r.stdout, r.stderr)

    def test_list_rules_names_the_trace_scope(self):
        env = dict(os.environ, PYTHONPATH=REPO)
        r = subprocess.run(
            [sys.executable, os.path.join(REPO, "tools",
                                          "tpudl_check.py"),
             "--list-rules"],
            capture_output=True, text=True, env=env, timeout=300)
        assert r.returncode == 0
        for rule in TRACE_RULES:
            assert rule in r.stdout
        assert "[trace]" in r.stdout
        assert "stale-suppression" in r.stdout
