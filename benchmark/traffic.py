"""The one traffic generator: a traffic file's parameters + a seed -> inputs.

A traffic mix is a JSON file in ``traffic/`` whose ``kind`` names one of the
generators below and whose other keys are that generator's parameters. The
same seed gives the same inputs; every seed gives the same sizes in another
order, so the seed does not change the amount of work.
"""

from __future__ import annotations

import os
from concurrent.futures import ThreadPoolExecutor


def jpeg_files(directory, n_files, sides, quality, seed, **_):
    """``n_files`` JPEGs with sides between ``sides`` = [lo, hi]:
    low-frequency content so they look like photographs to the codec (pure
    noise is its worst case), unique per file (``chip_smoke.write_jpegs``).
    The set of sizes is the same for every seed (drawn from generator 0) and
    the seed deals them out in another order with other content, so a seed
    changes the pixels and not the amount of decoding. Resized and encoded
    on a small pool."""
    import numpy as np
    from PIL import Image

    lo, hi = sides
    sizes = np.random.default_rng(0).integers(lo, hi + 1, size=(n_files, 2))
    rng = np.random.default_rng(seed)
    jobs = []
    for i, (h, w) in enumerate(rng.permutation(sizes).tolist()):
        base = rng.integers(0, 256, size=(h // 8 + 1, w // 8 + 1, 3),
                            dtype=np.uint8)
        jobs.append((os.path.join(directory, f"img_{i:05d}.jpg"), base, w, h))

    def write(job):
        path, base, w, h = job
        Image.fromarray(base).resize((w, h), Image.BILINEAR).save(
            path, quality=quality)

    with ThreadPoolExecutor(max_workers=4) as pool:
        list(pool.map(write, jobs))
    return {"directory": directory, "n": n_files}


def band_batches(n_batches, bands, seed, *, batch, side, classes, **_):
    """``n_batches`` uint8 image batches of ``bands`` separable classes
    (class = which horizontal band is bright) with one-hot labels over
    ``classes`` (``chip_smoke.phase_train``'s data)."""
    import numpy as np

    rng = np.random.default_rng(seed)
    xs, ys = [], []
    for _ in range(n_batches):
        cls = rng.integers(0, bands, size=batch)
        x = rng.integers(0, 96, size=(batch, side, side, 3), dtype=np.uint8)
        for i, c in enumerate(cls):
            x[i, c * side // bands:(c + 1) * side // bands] += 128
        xs.append(x)
        ys.append(np.eye(classes, dtype=np.float32)[cls])
    return {"xs": xs, "ys": ys}


GENERATORS = {"jpeg_files": jpeg_files, "band_batches": band_batches}


def generate(traffic: dict, seed: int, **sizes):
    """Run the generator ``traffic['kind']`` names. ``sizes`` are what the
    configuration fixes (batch, image side) or the adapter provides (a
    directory); the traffic file's own keys are the mix."""
    params = {k: v for k, v in traffic.items()
              if k not in ("kind", "why", "rehearse")}
    return GENERATORS[traffic["kind"]](seed=seed, **params, **sizes)
