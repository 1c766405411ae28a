"""The one general traffic generator. A mix is a data file under
``benchmarks/traffic/``; everything that tells one mix from another is a
parameter read from it, so a new mix is a new file and no code.

Serving kinds (driver ``serve``):

``closed_backlog``  ``outstanding`` requests are kept in the engine at all
                    times: a finished one is replaced at once. The first
                    ``stagger_first`` get budgets from a FIXED evenly spaced
                    set, dealt out in an order drawn from the seed, so the
                    slots stand at spread-out frontiers as in a service that
                    has run for a while, and every seed does the same work.

Training kind (driver ``train``): ``train_job``: per step a fresh host batch
of ``batch`` images and captions drawn from the seed.

A request's prompt is a caption of ``caption_tokens`` ids (log-uniform
length, ids uniform in ``[1, num_text_tokens)``), zero-padded to the text
length; ``samples_per_caption`` consecutive requests share one caption and
differ in their sampling seed.
"""

from __future__ import annotations

import json
import pathlib
from dataclasses import dataclass

import numpy as np

HERE = pathlib.Path(__file__).resolve().parent


def load(name: str, directory=None) -> dict:
    return json.loads((pathlib.Path(directory or HERE / "traffic") / f"{name}.json").read_text())


def rng_for(seed: int, stream: str) -> np.random.Generator:
    """Independent numpy streams from one ``--seed`` (any whole number)."""
    return np.random.default_rng([int(seed), sum(stream.encode())])


def caption(rng: np.random.Generator, spec: dict, text_seq_len: int,
            num_text_tokens: int) -> np.ndarray:
    lo, hi = spec["min"], min(spec["max"], text_seq_len)
    if spec.get("dist", "log_uniform") == "log_uniform":
        n = int(round(np.exp(rng.uniform(np.log(lo), np.log(hi)))))
    else:
        n = int(rng.integers(lo, hi + 1))
    row = np.zeros((text_seq_len,), np.int32)
    row[:n] = rng.integers(1, num_text_tokens, size=n)
    return row


@dataclass
class Planned:
    """One request as the generator planned it."""

    index: int
    prompt: np.ndarray
    max_new_tokens: int
    seed: int


class RequestStream:
    """Requests in the order they are to be sent, made lazily from the seed."""

    def __init__(self, mix: dict, cfg: dict, seed: int):
        self.mix, self.cfg = mix, cfg
        self._rng = rng_for(seed, "requests")
        self._base_seed = int(rng_for(seed, "sampling").integers(1, 2**30))
        self._n = 0
        self._caption = None
        self._per_caption = int(mix.get("samples_per_caption", 1))

    def next(self, max_new_tokens: int | None = None) -> Planned:
        if self._n % self._per_caption == 0:
            self._caption = caption(
                self._rng, self.mix["caption_tokens"],
                self.cfg["text_seq_len"], self.cfg["num_text_tokens"],
            )
        planned = Planned(
            index=self._n,
            prompt=self._caption,
            max_new_tokens=int(max_new_tokens or self.mix["max_new_tokens"]),
            seed=(self._base_seed + self._n) % (2**31 - 1),
        )
        self._n += 1
        return planned


def stagger_budgets(mix: dict, seed: int) -> list:
    """The first requests' budgets: evenly spaced over (0, max_new_tokens],
    the same set for every seed, dealt in an order drawn from the seed."""
    n, top = int(mix.get("stagger_first", 0)), int(mix["max_new_tokens"])
    budgets = [max(1, round(top * (i + 1) / n)) for i in range(n)]
    rng_for(seed, "stagger").shuffle(budgets)
    return budgets


def train_batch(mix: dict, cfg: dict, seed: int, step: int) -> dict:
    """One host batch: images (b, s, s, 3) float32 in [0, 1] and captions
    (b, text_seq_len) int32, every row different, from (seed, step)."""
    rng = np.random.default_rng([int(seed), 7, int(step)])
    b, s = int(mix["batch"]), cfg["vae"]["image_size"]
    image = rng.random((b, s, s, 3), dtype=np.float32)
    text = np.stack([
        caption(rng, mix["caption_tokens"], cfg["text_seq_len"], cfg["num_text_tokens"])
        for _ in range(b)
    ])
    return {"image": image, "text": text}
