"""30 s log-mel segments with their transcripts, for an encoder-decoder
recognizer, kept here so the yardstick does not move when the program's
data code does.

Each segment has ``tokens`` + 1 token ids drawn with Zipf priors over
the vocabulary (``vocab``, the configuration's), as subword units of
speech are; the first ``tokens`` are the teacher-forced decoder input and
the last ``tokens`` the labels (the next token of each).  The segment's
``frames`` log-mel-like frames of ``n_mels`` bins are spread evenly over
its labels: each frame is its label's acoustic unit (one of ``classes``,
by id) centroid plus Gaussian noise of scale ``noise``.
``distinct_batches`` global batches of ``learners x batch_per_learner``
segments; every seed gets the same sizes, only the values differ.
"""
import numpy as np


def _rng(seed: int, *stream: int) -> np.random.Generator:
    return np.random.default_rng([int(seed) % 2 ** 63, *stream])


def batches(traffic: dict, n_mels: int, seed: int) -> list:
    B = int(traffic["learners"]) * int(traffic["batch_per_learner"])
    T, S = int(traffic["frames"]), int(traffic["tokens"])
    V, k = int(traffic["vocab"]), int(traffic["classes"])
    r = _rng(seed, 0)
    centroids = r.standard_normal((k, n_mels), dtype=np.float32)
    priors = 1.0 / np.arange(1, V + 1)
    priors /= priors.sum()
    at = np.arange(T) * S // T          # the label each frame belongs to
    out = []
    for i in range(int(traffic["distinct_batches"])):
        r = _rng(seed, 1, i)
        ids = r.choice(V, size=(B, S + 1), p=priors).astype(np.int32)
        mel = centroids[ids[:, 1:][:, at] % k]
        mel += np.float32(traffic["noise"]) * r.standard_normal(
            mel.shape, dtype=np.float32)
        out.append({"mel": mel, "tokens": ids[:, :-1], "labels": ids[:, 1:]})
    return out
