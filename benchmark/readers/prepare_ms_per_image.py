"""Host milliseconds of decode + resize + pack per image: the pipeline
report's ``prepare`` seconds, summed over the prepare workers (so it can
exceed the pass's wall time), over the timed passes' images."""


def read(facts):
    pipe = facts.get("pipeline")
    if not pipe or not facts.get("images"):
        return None
    return 1e3 * pipe["prepare_s"] / facts["images"]
