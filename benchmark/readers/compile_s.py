"""Seconds of tracing, lowering and compiling (or loading from the
persistent cache) during set-up, from ``jax.monitoring`` durations."""


def read(facts):
    return facts.get("setup", {}).get("compile_s")
