"""The one traffic generator: a training job's data, made on the host from
``--seed`` and the parameters in the cell's workload file. The program under
test receives arrays and never the seed.

Both generators are copies of ``mpit_tpu/data/synthetic.py`` (PERF.md lists
the originals), changed only so that set-up stays short: the token chain is
vectorised over windows, and the image pool draws its classes and its noise
from small banks. Every seed gives the same shapes and the same number of
samples, so the seed changes the values and the order and never the work.
"""

import numpy as np


def token_windows(seed: int, num_windows: int, seq_len: int, vocab_size: int):
    """``(x, y)`` int32 ``(num_windows, seq_len)``: each window an
    independent sparse first-order Markov chain (four likely successors a
    context, 10% uniform noise: ``synthetic_lm_corpus``'s structure, so a
    model's loss can fall), ``y`` the window shifted by one token."""
    rng = np.random.default_rng(seed)
    branch = 4
    successors = rng.integers(0, vocab_size, size=(vocab_size, branch))
    tokens = np.empty((num_windows, seq_len + 1), dtype=np.int32)
    tokens[:, 0] = rng.integers(0, vocab_size, size=num_windows)
    picks = rng.integers(0, branch, size=(seq_len + 1, num_windows))
    noisy = rng.random((seq_len + 1, num_windows)) < 0.1
    randoms = rng.integers(0, vocab_size, size=(seq_len + 1, num_windows))
    for t in range(1, seq_len + 1):
        follow = successors[tokens[:, t - 1], picks[t]]
        tokens[:, t] = np.where(noisy[t], randoms[t], follow)
    return tokens[:, :-1].copy(), tokens[:, 1:].copy()


def image_pool(
    seed: int,
    num_images: int,
    image_size: int,
    num_classes: int,
    pool_classes: int,
    noise: float = 0.35,
):
    """``(x, y)``: float32 NHWC images in [0, 1] and int32 labels. A sample
    is ``clip(intensity * template[class] + noise)`` as in
    ``synthetic_image_classification``, with two economies so that a pool at
    224 px is made in about a second: templates exist only for the
    ``pool_classes`` of the ``num_classes`` labels the pool draws (1,000
    would be 602 MB), and a sample's noise is one of ``pool_classes`` noise
    images, drawn independently of its class, instead of fresh normals for
    every pixel of every sample. The pool is written once, a block at a time.
    """
    rng = np.random.default_rng(seed)
    shape = (image_size, image_size, 3)
    classes = rng.choice(num_classes, size=pool_classes, replace=False)
    templates = rng.random((pool_classes, *shape), dtype=np.float32)
    noises = rng.standard_normal((pool_classes, *shape), dtype=np.float32)
    noises *= np.float32(noise)
    which = rng.integers(0, pool_classes, size=num_images)
    which_noise = rng.integers(0, pool_classes, size=num_images)
    intensity = rng.uniform(0.7, 1.3, size=(num_images, 1, 1, 1)).astype(
        np.float32
    )
    x = np.empty((num_images, *shape), dtype=np.float32)
    for lo in range(0, num_images, 64):
        block = slice(lo, lo + 64)
        np.multiply(templates[which[block]], intensity[block], out=x[block])
        x[block] += noises[which_noise[block]]
        np.clip(x[block], 0.0, 1.0, out=x[block])
    return x, classes[which].astype(np.int32)


class Repeated:
    """A pool seen ``repeats`` times over: index ``i`` is ``pool[i % n]``.

    ``data.Batches`` shuffles ``len(x)`` indices an epoch and drains the
    prefetch queue at each epoch's end. A pool small enough to make in
    set-up would put such a boundary every few steps, where a user's data
    set puts one every few thousand; this keeps the pool small and the
    epochs long. The gather a batch costs is unchanged."""

    def __init__(self, pool: np.ndarray, repeats: int):
        self.pool = pool
        self.repeats = int(repeats)
        self.shape = (len(pool) * self.repeats, *pool.shape[1:])
        self.dtype = pool.dtype

    def __len__(self) -> int:
        return self.shape[0]

    def __getitem__(self, idx):
        return self.pool[np.asarray(idx) % len(self.pool)]


def make(seed: int, data: dict, seq_len=None, image_size=None,
         vocab_size=None, num_classes=None):
    """The job's ``(x, y)`` as the workload file's ``data`` block asks, at
    the sizes the cell's configuration gives."""
    kind = data["kind"]
    if kind == "tokens":
        x, y = token_windows(seed, data["pool"], seq_len, vocab_size)
    elif kind == "images":
        x, y = image_pool(
            seed, data["pool"], image_size, num_classes, data["pool_classes"],
        )
    else:
        raise ValueError(f"unknown data kind {kind!r}; have: tokens, images")
    repeats = data.get("epoch_repeats", 1)
    return Repeated(x, repeats), Repeated(y, repeats)
