"""Handwritten digits (sklearn's bundled UCI optdigits) — the one
REAL image-classification dataset available in this air-gapped build.

The ladder's configs 2-3 (MNIST softmax, Fashion-MNIST MLP) fall back
to synthetic generators when their IDX files are absent
(``datasets/mnist.py``), which makes their accuracy numbers
incomparable to anything. This dataset exists to anchor those model
families against real data anyway: 1,797 genuine 8x8 grayscale digit
scans (UCI ML hand-written digits, shipped inside scikit-learn — zero
network), same 10-class problem shape, run through the SAME linear /
MLP models and train loop.
"""

from __future__ import annotations

import numpy as np

from mlapi_tpu.datasets import SupervisedSplits, register_dataset
from mlapi_tpu.utils.vocab import LabelVocab


@register_dataset("digits")
def load_digits(
    *, test_fraction: float = 0.20, seed: int = 1
) -> SupervisedSplits:
    """1,797 real 8x8 digit scans → 64 features in [0, 1], split
    80/20 with the same splitter convention as the Iris config."""
    from sklearn.datasets import load_digits as _sk_load_digits
    from sklearn.model_selection import train_test_split as _sk_split

    raw = _sk_load_digits()
    x = (raw.data / 16.0).astype(np.float32)  # [1797, 64], pixel max 16
    labels = np.asarray([str(t) for t in raw.target])
    vocab = LabelVocab.from_labels(labels)
    y = vocab.encode(labels)

    x_train, x_test, y_train, y_test = _sk_split(
        x, y, test_size=test_fraction, random_state=seed, shuffle=True,
        stratify=y,
    )
    return SupervisedSplits(
        x_train=x_train,
        y_train=y_train.astype(np.int32),
        x_test=x_test,
        y_test=y_test.astype(np.int32),
        vocab=vocab,
        feature_names=tuple(f"px_{i}" for i in range(x.shape[1])),
        source="real",
    )
