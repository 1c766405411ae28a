"""Folder dataset + host-side data loading.

Re-owns the reference's ``TextImageDataset`` (loader.py:10-99): images paired
with same-stem ``.txt`` caption files, one random caption per sample, a
1:1-ratio RandomResizedCrop, and corrupt-file resilience (skip to a
random/next sample on decode error, loader.py:58-69,79-96).

TPU-shaped differences: samples come out as numpy NHWC float32 in [0, 1]
(batch crosses the host->device boundary once, as one array), the loader
shards deterministically across hosts (replacing torch's DistributedSampler,
train_dalle.py:391-398), and batching runs in a background prefetch thread so
host decode overlaps device compute.
"""

from __future__ import annotations

import queue
import random
import threading
from pathlib import Path
from typing import Iterator, List, Optional, Tuple

import numpy as np
from PIL import Image

IMAGE_EXTS = (".jpg", ".jpeg", ".png", ".bmp")


def random_resized_crop(
    img: Image.Image,
    out_size: int,
    rng: random.Random,
    min_scale: float = 0.75,
) -> Image.Image:
    """Square random crop covering a random [min_scale, 1] area fraction,
    resized to out_size (reference loader.py:46-53: RandomResizedCrop with
    ratio (1, 1) and scale (resize_ratio, 1))."""
    w, h = img.size
    area = w * h
    for _ in range(10):
        target = rng.uniform(min_scale, 1.0) * area
        side = int(round(target**0.5))
        if side <= w and side <= h:
            left = rng.randint(0, w - side)
            top = rng.randint(0, h - side)
            img = img.crop((left, top, left + side, top + side))
            break
    else:  # degenerate aspect ratios: center-crop the largest square
        side = min(w, h)
        left, top = (w - side) // 2, (h - side) // 2
        img = img.crop((left, top, left + side, top + side))
    return img.resize((out_size, out_size), Image.BICUBIC)


def image_to_array(img: Image.Image) -> np.ndarray:
    """RGB(A)/L -> (h, w, 3) float32 in [0, 1] (the reference's ToTensor,
    NHWC instead of NCHW)."""
    img = img.convert("RGB")
    return np.asarray(img, dtype=np.float32) / 255.0


class TextImageDataset:
    def __init__(
        self,
        folder: str,
        text_len: int = 256,
        image_size: int = 128,
        truncate_captions: bool = False,
        resize_ratio: float = 0.75,
        tokenizer=None,
        shuffle: bool = False,
        seed: int = 0,
    ):
        self.shuffle = shuffle
        path = Path(folder)

        text_files = {p.stem: p for p in path.glob("**/*.txt")}
        image_files = {
            p.stem: p for ext in IMAGE_EXTS for p in path.glob(f"**/*{ext}")
        }
        keys = image_files.keys() & text_files.keys()
        self.keys = sorted(keys)
        self.text_files = {k: text_files[k] for k in self.keys}
        self.image_files = {k: image_files[k] for k in self.keys}
        self.text_len = text_len
        self.truncate_captions = truncate_captions
        self.resize_ratio = resize_ratio
        self.image_size = image_size
        if tokenizer is None:
            from .tokenizers import get_tokenizer

            tokenizer = get_tokenizer()
        self.tokenizer = tokenizer
        self._rng = random.Random(seed)

    def __len__(self) -> int:
        return len(self.keys)

    def random_sample(self):
        return self[self._rng.randint(0, len(self) - 1)]

    def sequential_sample(self, ind: int):
        return self[(ind + 1) % len(self)]

    def skip_sample(self, ind: int):
        if self.shuffle:
            return self.random_sample()
        return self.sequential_sample(ind)

    def __getitem__(self, ind: int) -> Tuple[np.ndarray, np.ndarray]:
        key = self.keys[ind]
        try:
            descriptions = [
                d for d in
                self.text_files[key].read_text(encoding="utf8").split("\n") if d
            ]
            description = self._rng.choice(descriptions)  # IndexError if empty
            tokens = self.tokenizer.tokenize(
                description, self.text_len, truncate_text=self.truncate_captions
            )[0]
        except (UnicodeDecodeError, OSError, IndexError):
            return self.skip_sample(ind)
        try:
            with Image.open(self.image_files[key]) as img:
                img = random_resized_crop(
                    img, self.image_size, self._rng, self.resize_ratio
                )
                image = image_to_array(img)
        except (OSError, ValueError):
            # corrupt image: substitute another sample (loader.py:83-96)
            return self.skip_sample(ind)
        return tokens, image


class DataLoader:
    """Host-side batcher with per-host sharding and background prefetch.

    Yields dict batches {"text": (b, text_len) int32, "image": (b, h, w, 3)
    float32} ready for one device_put. ``process_index/process_count`` shard
    the sample space across hosts the way the reference's DistributedSampler
    does across ranks (train_dalle.py:391-398).
    """

    def __init__(
        self,
        dataset,
        batch_size: int,
        shuffle: bool = True,
        drop_last: bool = True,
        seed: int = 0,
        process_index: int = 0,
        process_count: int = 1,
        prefetch: int = 2,
        collate_fn=None,
    ):
        assert batch_size >= 1
        if collate_fn is not None:
            self._collate = collate_fn
        self.dataset = dataset
        self.batch_size = batch_size
        self.shuffle = shuffle
        self.drop_last = drop_last
        self.seed = seed
        self.process_index = process_index
        self.process_count = process_count
        self.prefetch = prefetch
        self.epoch = 0

    def __len__(self) -> int:
        n = len(self.dataset) // self.process_count
        if self.drop_last:
            return n // self.batch_size
        return -(-n // self.batch_size)

    def _indices(self) -> List[int]:
        idx = list(range(len(self.dataset)))
        if self.shuffle:
            random.Random(self.seed + self.epoch).shuffle(idx)
        # wrap-pad so every host yields the SAME number of samples/batches —
        # unequal counts would deadlock lockstep collectives at the epoch
        # boundary (torch's DistributedSampler pads the same way)
        per = -(-len(idx) // self.process_count)
        idx = idx + idx[: per * self.process_count - len(idx)]
        return idx[self.process_index :: self.process_count]

    def _produce(self, out_q: queue.Queue):
        try:
            batch: List[Tuple[np.ndarray, np.ndarray]] = []
            for i in self._indices():
                sample = self.dataset[i]
                if sample is None:
                    continue
                batch.append(sample)
                if len(batch) == self.batch_size:
                    out_q.put(self._collate(batch))
                    batch = []
            if batch and not self.drop_last:
                out_q.put(self._collate(batch))
        finally:
            out_q.put(None)

    @staticmethod
    def _collate(batch):
        text = np.stack([b[0] for b in batch]).astype(np.int32)
        image = np.stack([b[1] for b in batch])
        return {"text": text, "image": image}

    def __iter__(self) -> Iterator[dict]:
        out_q: queue.Queue = queue.Queue(maxsize=self.prefetch)
        worker = threading.Thread(target=self._produce, args=(out_q,), daemon=True)
        worker.start()
        while True:
            item = out_q.get()
            if item is None:
                break
            yield item
        worker.join()
        self.epoch += 1


class ImageFolderDataset:
    """Label-free image folder for VAE training (the reference uses
    torchvision ImageFolder, train_vae.py:107-115; labels were discarded)."""

    def __init__(self, folder: str, image_size: int, seed: int = 0):
        path = Path(folder)
        self.files = sorted(
            p for ext in IMAGE_EXTS for p in path.glob(f"**/*{ext}")
        )
        assert len(self.files) > 0, f"no images found at {folder}"
        self.image_size = image_size
        self._rng = random.Random(seed)

    def __len__(self) -> int:
        return len(self.files)

    def __getitem__(self, ind: int) -> Tuple[np.ndarray, np.ndarray]:
        try:
            with Image.open(self.files[ind]) as img:
                img = random_resized_crop(img, self.image_size, self._rng, 0.75)
                arr = image_to_array(img)
        except (OSError, ValueError):
            return self[(ind + 1) % len(self)]
        return arr, np.zeros((), np.int32)

    @staticmethod
    def collate(batch):
        return {"image": np.stack([b[0] for b in batch])}


class PackedTextDataset:
    """Language-model rows from a folder of ``.txt`` documents: every document
    tokenized, followed by one end-of-text id, all packed end to end into one
    stream and cut into rows of ``seq_len`` ids (the tail that fills no row is
    dropped). No mask and no state reset at document boundaries: a row is one
    causal sequence. Pairs with ``DataLoader(..., collate_fn=PackedTextDataset.collate)``."""

    def __init__(self, folder: str, seq_len: int, tokenizer, eot_id: int = 0):
        stream: List[int] = []
        for path in sorted(Path(folder).rglob("*.txt")):
            stream.extend(tokenizer.encode(path.read_text(errors="replace")))
            stream.append(eot_id)
        rows = len(stream) // seq_len
        self.rows = np.asarray(stream[: rows * seq_len], np.int32).reshape(rows, seq_len)

    def __len__(self) -> int:
        return len(self.rows)

    def __getitem__(self, ind: int) -> np.ndarray:
        return self.rows[ind]

    @staticmethod
    def collate(batch):
        return {"ids": np.stack(batch)}
