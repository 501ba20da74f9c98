"""Write the device trace ``tests/fixtures/v5e_trace/`` holds.

One step of a small program with three named scopes (``lm.norm``,
``lm.attention``, ``lm.head``) and an unscoped tail, traced on a TPU.
``tests/test_obs_trace.py`` reads the trace back through
``tpudl.obs.trace.device_account``: every operation filed once must
make up the step to within 1%. The matrix products are large enough
(4096 x 4096 x 2048 in bf16, about 0.35 ms each on a v5e) that the idle
time between operations stays well under that.

    python tests/fixtures/make_chip_trace.py <out_dir>

writes ``<out_dir>/plugins/profile/<time>/<host>.xplane.pb`` and prints
the account. Run it on the chip; a CPU backend writes no device plane.
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))


def main(out_dir: str) -> int:
    import jax
    import jax.numpy as jnp

    from tpudl.obs import trace as T

    def step(x, w_v, w_head, w_out):
        with T.named_scope("lm.norm"):
            h = x * jax.lax.rsqrt(
                jnp.mean(jnp.square(x.astype(jnp.float32)), axis=-1,
                         keepdims=True) + 1e-6).astype(x.dtype)
        with T.named_scope("lm.attention"):
            scores = (h @ h.T).astype(jnp.float32) / 45.0
            h = jax.nn.softmax(scores, axis=-1).astype(x.dtype) @ (h @ w_v)
        with T.named_scope("lm.head"):
            logits = h @ w_head
        return jnp.sum(jnp.tanh(logits @ w_out).astype(jnp.float32))

    keys = jax.random.split(jax.random.PRNGKey(0), 4)
    shapes = ((4096, 2048), (2048, 2048), (2048, 4096), (4096, 2048))
    args = [jax.device_put(jax.random.normal(k, s, jnp.bfloat16) * 0.03)
            for k, s in zip(keys, shapes)]
    jitted = jax.jit(step)
    jax.block_until_ready(jitted(*args))  # compile outside the trace
    with T.profile(out_dir):
        jax.block_until_ready(jitted(*args))
    table = T.device_account(out_dir)
    print({k: v for k, v in table.items() if k != "scopes"})
    for entry in table.get("scopes", []):
        print(f"  {entry['scope']:<14} {entry['ms']:.4f} ms "
              f"({entry['ops']} ops)")
    return 0 if table else 1


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
