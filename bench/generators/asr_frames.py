"""CD-state frame classification, a copy of the program's synthetic ASR
data (``repro.data.pipeline.SyntheticASRDataset``), kept here so the
yardstick does not move when the program's data code does.

Labels are drawn from ``classes`` states with Zipf priors; each frame's
features are its state's centroid plus Gaussian noise of scale
``noise``.  ``distinct_batches`` global batches of ``learners x
batch_per_learner`` rows of ``frames`` frames; every seed gets the same
sizes, only the values differ.
"""
import numpy as np


def _rng(seed: int, *stream: int) -> np.random.Generator:
    return np.random.default_rng([int(seed) % 2 ** 63, *stream])


def batches(traffic: dict, d_in: int, seed: int) -> list:
    B = int(traffic["learners"]) * int(traffic["batch_per_learner"])
    T, k = int(traffic["frames"]), int(traffic["classes"])
    r = _rng(seed, 0)
    centroids = r.standard_normal((k, d_in), dtype=np.float32)
    priors = 1.0 / np.arange(1, k + 1)
    priors /= priors.sum()
    out = []
    for i in range(int(traffic["distinct_batches"])):
        r = _rng(seed, 1, i)
        cls = r.choice(k, size=(B, T), p=priors)
        feats = centroids[cls]
        feats += np.float32(traffic["noise"]) * r.standard_normal(
            feats.shape, dtype=np.float32)
        out.append({"features": feats, "labels": cls.astype(np.int32)})
    return out
