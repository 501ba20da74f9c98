"""Share of the timed passes' wall time that the executor's consumer spent
waiting for a prepared batch (pipeline report, ``infeed_wait``)."""


def read(facts):
    pipe = facts.get("pipeline")
    if not pipe or not pipe.get("pass_wall_s"):
        return None
    return 100.0 * pipe["infeed_wait_s"] / pipe["pass_wall_s"]
