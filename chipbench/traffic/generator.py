"""The one traffic generator: a traffic mix is a JSON file of parameters.

A mix names the shape, dtype and range of one sample's ``input`` and how
its ``label`` is made, in terms of the configuration's own keys; the
generator draws ``cache_batches`` global batches of samples from the seed
once, holds them in host memory and serves them in order, cycling, as a
map-style dataset for ``tpuframe.data.DataLoader``.  Every seed gives the
same sizes in another draw.
"""

from __future__ import annotations

import json
import os

import numpy as np

_HERE = os.path.dirname(os.path.abspath(__file__))

#: steps an "epoch" of the cycled cache lasts: far more than any window,
#: so no epoch boundary (prefetcher rebuild) falls inside a run
STEPS_PER_EPOCH = 4096


def load_mix(name: str) -> dict:
    with open(os.path.join(_HERE, f"{name}.json")) as f:
        return json.load(f)


def _dim(value, cfg: dict) -> int:
    """An int, a configuration key, or ``key+int``."""
    if isinstance(value, int):
        return value
    key, _, plus = str(value).partition("+")
    base = int(key) if key.isdigit() else int(cfg[key])
    return base + (int(plus) if plus else 0)


def _draw(rng: np.random.Generator, n: int, spec: dict, cfg: dict) -> np.ndarray:
    shape = (n,) + tuple(_dim(d, cfg) for d in spec["shape"])
    dtype = np.dtype(spec["dtype"])
    high = _dim(spec["high"], cfg)
    if dtype == np.uint8 and high == 256:
        # whole 64-bit words viewed as bytes: an order faster than drawing
        # bytes one by one, and the image set is most of the data made
        count = int(np.prod(shape))
        words = rng.integers(0, 2**64, -(-count // 8), dtype=np.uint64)
        return words.view(np.uint8)[:count].reshape(shape)
    return rng.integers(0, high, shape, dtype=dtype)


class CachedSamples:
    """Map-style dataset over a seeded in-memory sample cache."""

    def __init__(self, inputs: np.ndarray, labels: np.ndarray, length: int,
                 num_classes: int | None):
        self.inputs, self.labels = inputs, labels
        self._length = length
        self.num_classes = num_classes

    def __len__(self) -> int:
        return self._length

    def __getitem__(self, i: int):
        j = i % len(self.inputs)
        return self.inputs[j], self.labels[j]

    def first_batches(self, n_batches: int, batch: int):
        """The first ``n_batches`` global batches exactly as an unshuffled
        ``DataLoader`` serves them: what the reference follows."""
        idx = np.arange(n_batches * batch) % len(self.inputs)
        return [
            (self.inputs[idx[b * batch:(b + 1) * batch]],
             self.labels[idx[b * batch:(b + 1) * batch]])
            for b in range(n_batches)
        ]


def make_dataset(mix: dict, cfg: dict, seed: int, global_batch: int) -> CachedSamples:
    rng = np.random.default_rng([int(seed), 0x7261])
    n = int(mix["cache_batches"]) * global_batch
    label = mix["label"]
    if "shift" in label:
        # next-token labels: draw rows one longer and split them
        shift = int(label["shift"])
        spec = dict(mix["input"])
        spec["shape"] = [f"{spec['shape'][0]}+{shift}"] + list(spec["shape"][1:])
        rows = _draw(rng, n, spec, cfg)
        inputs = np.ascontiguousarray(rows[:, :-shift])
        labels = np.ascontiguousarray(rows[:, shift:])
        num_classes = None
    else:
        inputs = _draw(rng, n, mix["input"], cfg)
        labels = _draw(rng, n, label, cfg)
        num_classes = _dim(label["high"], cfg)
    return CachedSamples(inputs, labels, STEPS_PER_EPOCH * global_batch, num_classes)
