"""Data layer tests: BPE tokenizer contract, folder dataset, loader batching,
tar-shard streaming."""

import io
import random
import tarfile
from pathlib import Path

import numpy as np
import pytest
from PIL import Image

from dalle_pytorch_tpu.data import (
    DataLoader,
    ImageFolderDataset,
    SimpleTokenizer,
    TarImageTextDataset,
    TarLoader,
    TextImageDataset,
    default_bpe_path,
    expand_urls,
)

needs_vocab = pytest.mark.skipif(
    default_bpe_path() is None, reason="bpe_simple_vocab_16e6.txt not available"
)


@pytest.fixture(scope="module")
def tok():
    if default_bpe_path() is None:
        pytest.skip("bpe vocab unavailable")
    return SimpleTokenizer()


@needs_vocab
class TestSimpleTokenizer:
    def test_vocab_size(self, tok):
        assert tok.vocab_size == 49408

    @pytest.mark.parametrize(
        "text",
        [
            "hello world",
            "a painting of a fox sitting in a field at sunrise",
            "Ünïcödé, accents & <html> entities!",
            "numbers 12345 and punctuation?!...",
        ],
    )
    def test_round_trip(self, tok, text):
        ids = tok.encode(text)
        assert ids and all(0 < i < tok.vocab_size for i in ids)
        out = tok.decode(ids)
        # byte-BPE round trip is lossy only in case/whitespace normalization
        # (decode re-spaces at every </w>, exactly like the reference's
        # .replace('</w>', ' '), tokenizer.py:134)
        import re

        norm = lambda s: re.sub(r"\s+", "", s.lower())
        assert norm(out) == norm(text)

    def test_tokenize_contract(self, tok):
        arr = tok.tokenize(["hi there", "a cat"], context_length=16)
        assert arr.shape == (2, 16) and arr.dtype == np.int32
        n = len(tok.encode("hi there"))
        assert (arr[0, n:] == 0).all() and (arr[0, :n] > 0).all()

    def test_tokenize_too_long(self, tok):
        long = "word " * 300
        with pytest.raises(RuntimeError):
            tok.tokenize(long, context_length=8)
        arr = tok.tokenize(long, context_length=8, truncate_text=True)
        assert arr.shape == (1, 8) and (arr > 0).all()

    def test_decode_skips_pads(self, tok):
        ids = tok.encode("blue bird")
        padded = ids + [49000, 49001]
        assert tok.decode(padded, pad_tokens={49000, 49001}) == tok.decode(ids)

    def test_known_clip_encoding(self, tok):
        """'hello world' under the standard CLIP vocab is [3306, 1002] —
        pins vocab construction (merge slicing, </w> handling) exactly."""
        assert tok.encode("hello world") == [3306, 1002]


def write_sample(folder, stem, caption="a red square", size=32, corrupt=False):
    img = Image.new("RGB", (size, size), (200, 30, 30))
    p = folder / f"{stem}.png"
    if corrupt:
        p.write_bytes(b"not an image at all")
    else:
        img.save(p)
    (folder / f"{stem}.txt").write_text(caption)


@needs_vocab
class TestTextImageDataset:
    def test_pairing_and_shapes(self, tmp_path):
        for i in range(4):
            write_sample(tmp_path, f"s{i}", caption=f"sample number {i}")
        (tmp_path / "orphan.txt").write_text("no image")  # unpaired: excluded
        ds = TextImageDataset(str(tmp_path), text_len=16, image_size=16)
        assert len(ds) == 4
        tokens, image = ds[0]
        assert tokens.shape == (16,) and tokens.dtype == np.int32
        assert image.shape == (16, 16, 3) and 0.0 <= image.min() <= image.max() <= 1.0

    def test_corrupt_image_skipped(self, tmp_path):
        write_sample(tmp_path, "bad", corrupt=True)
        write_sample(tmp_path, "good")
        ds = TextImageDataset(str(tmp_path), text_len=8, image_size=16)
        tokens, image = ds[ds.keys.index("bad")]
        assert image.shape == (16, 16, 3)  # substituted with the good sample

    def test_empty_caption_skipped(self, tmp_path):
        write_sample(tmp_path, "a")
        (tmp_path / "b.png").write_bytes((tmp_path / "a.png").read_bytes())
        (tmp_path / "b.txt").write_text("")
        ds = TextImageDataset(str(tmp_path), text_len=8, image_size=16)
        tokens, _ = ds[ds.keys.index("b")]
        assert (tokens > 0).any()  # substitute had a real caption


@needs_vocab
class TestDataLoader:
    def test_batching_and_sharding(self, tmp_path):
        for i in range(10):
            write_sample(tmp_path, f"s{i}")
        ds = TextImageDataset(str(tmp_path), text_len=8, image_size=16)
        dl = DataLoader(ds, batch_size=2, shuffle=True, seed=1)
        batches = list(dl)
        assert len(batches) == 5
        assert batches[0]["text"].shape == (2, 8)
        assert batches[0]["image"].shape == (2, 16, 16, 3)

        # two-host sharding: disjoint and half-size
        dl0 = DataLoader(ds, 2, shuffle=False, process_index=0, process_count=2)
        dl1 = DataLoader(ds, 2, shuffle=False, process_index=1, process_count=2)
        assert len(dl0) == len(dl1) == 2
        assert set(dl0._indices()).isdisjoint(dl1._indices())

    def test_image_folder(self, tmp_path):
        for i in range(3):
            Image.new("RGB", (24, 24), (i * 40, 0, 0)).save(tmp_path / f"i{i}.png")
        ds = ImageFolderDataset(str(tmp_path), image_size=16)
        dl = DataLoader(
            ds, batch_size=3, shuffle=False, collate_fn=ImageFolderDataset.collate
        )
        (batch,) = list(dl)
        assert batch["image"].shape == (3, 16, 16, 3)


class TestExpandUrls:
    def test_braces(self):
        urls = expand_urls("shard-{0000..0003}.tar")
        assert urls == [f"shard-{i:04d}.tar" for i in range(4)]

    def test_plain(self):
        assert expand_urls("/x/y.tar") == ["/x/y.tar"]


@needs_vocab
class TestTarPipeline:
    def make_shard(self, path, n=4, start=0, with_bad=False):
        with tarfile.open(path, "w") as tf:
            for i in range(start, start + n):
                img = Image.new("RGB", (24, 24), (10 * i, 20, 30))
                buf = io.BytesIO()
                img.save(buf, format="PNG")
                self._add(tf, f"sample{i:04d}.png", buf.getvalue())
                self._add(tf, f"sample{i:04d}.txt", f"caption {i}".encode())
            if with_bad:
                self._add(tf, "bad0001.png", b"garbage bytes")
                self._add(tf, "bad0001.txt", b"broken image")

    @staticmethod
    def _add(tf, name, data):
        info = tarfile.TarInfo(name)
        info.size = len(data)
        tf.addfile(info, io.BytesIO(data))

    def test_stream_and_batch(self, tmp_path):
        self.make_shard(tmp_path / "shard-0000.tar", n=4, start=0)
        self.make_shard(tmp_path / "shard-0001.tar", n=4, start=4)
        ds = TarImageTextDataset(
            str(tmp_path / "shard-{0000..0001}.tar"), text_len=8, image_size=16
        )
        samples = list(ds)
        assert len(samples) == 8
        batches = list(TarLoader(ds, batch_size=4))
        assert len(batches) == 2
        assert batches[0]["text"].shape == (4, 8)
        assert batches[0]["image"].shape == (4, 16, 16, 3)

    def test_warn_and_continue(self, tmp_path, capsys):
        self.make_shard(tmp_path / "s.tar", n=2, with_bad=True)
        ds = TarImageTextDataset(str(tmp_path / "s.tar"), text_len=8, image_size=16)
        samples = list(ds)
        assert len(samples) == 2  # bad sample dropped, stream continued

    def test_host_sharding(self, tmp_path):
        for i in range(4):
            self.make_shard(tmp_path / f"shard-{i:04d}.tar", n=2, start=2 * i)
        spec = str(tmp_path / "shard-{0000..0003}.tar")
        a = TarImageTextDataset(spec, text_len=8, image_size=16, process_index=0, process_count=2)
        b = TarImageTextDataset(spec, text_len=8, image_size=16, process_index=1, process_count=2)
        assert set(a._my_shards()).isdisjoint(b._my_shards())
        assert len(list(a)) == len(list(b)) == 4


class TestMetricsLogger:
    """§5.5 observability additions: histogram + artifact upload (the
    reference logs wandb.Histogram(codes) in train_vae.py:262 and uploads
    checkpoint artifacts in train_dalle.py:637-649)."""

    class FakeWandb:
        def __init__(self):
            self.logged, self.artifacts = [], []
            self.run = self

        def Histogram(self, v):
            return ("hist", np.asarray(v).shape)

        def log(self, d, step=None):
            self.logged.append((d, step))

        def Artifact(self, name, type="model", metadata=None):
            class A:
                def __init__(self):
                    self.name, self.type, self.metadata = name, type, metadata
                    self.files = []

                def add_file(self, p):
                    self.files.append(p)

            return A()

        def log_artifact(self, a):
            self.artifacts.append(a)

        def finish(self):
            pass

    def test_histogram_and_artifact_with_wandb(self, tmp_path):
        from dalle_pytorch_tpu.utils.metrics import MetricsLogger

        logger = MetricsLogger(enabled=True)
        logger._wandb = self.FakeWandb()
        logger.log_histogram("codes", np.arange(12).reshape(3, 4), step=7)
        (d, step), = logger._wandb.logged
        assert step == 7 and d["codes"] == ("hist", (12,))

        f = tmp_path / "m.ckpt"
        f.write_bytes(b"x")
        part = tmp_path / "m.ckpt.0123456789ab.part0000"
        part.write_bytes(b"y")
        logger.log_artifact("trained-vae", str(f), metadata={"dim": 8})
        (a,) = logger._wandb.artifacts
        assert a.name == "trained-vae" and a.files == [str(f), str(part)]
        assert a.metadata == {"dim": 8}

    def test_noop_without_wandb(self, capsys):
        from dalle_pytorch_tpu.utils.metrics import MetricsLogger

        logger = MetricsLogger(enabled=True)
        logger.log_histogram("codes", np.asarray([1, 1, 2, 5]), step=0)
        logger.log_artifact("x", "/nonexistent/path")  # must not raise
        out = capsys.readouterr().out
        assert "histogram" in out and "unique=3" in out


def test_analyze_trace_tool(tmp_path):
    """tools/analyze_trace.py digests a Chrome-format profiler trace into
    the per-category table."""
    import gzip
    import json
    import sys

    tools = Path(__file__).resolve().parent.parent / "tools"
    sys.path.insert(0, str(tools))
    import analyze_trace

    events = [
        {"ph": "M", "name": "thread_name", "pid": 1, "tid": 1,
         "args": {"name": "XLA Modules"}},
        {"ph": "M", "name": "thread_name", "pid": 1, "tid": 2,
         "args": {"name": "XLA Ops"}},
        {"ph": "X", "pid": 1, "tid": 1, "name": "jit_train_step(123)",
         "ts": 0.0, "dur": 100.0, "args": {}},
        {"ph": "X", "pid": 1, "tid": 2, "name": "fusion.7", "ts": 1.0,
         "dur": 60.0, "args": {"hlo_category": "convolution fusion",
                               "deduplicated_name": "fusion.1"}},
        {"ph": "X", "pid": 1, "tid": 2, "name": "fn.3", "ts": 62.0,
         "dur": 30.0, "args": {"hlo_category": "custom-call"}},
        # outside the module window: must be excluded
        {"ph": "X", "pid": 1, "tid": 2, "name": "fusion.9", "ts": 200.0,
         "dur": 50.0, "args": {"hlo_category": "loop fusion"}},
    ]
    out = analyze_trace.analyze(events, None, 10)
    assert "jit_train_step" in out
    assert "convolution fusion" in out and "custom-call" in out
    assert "loop fusion" not in out  # outside the window
    d = tmp_path / "prof"
    (d / "plugins" / "profile" / "x").mkdir(parents=True)
    with gzip.open(d / "plugins" / "profile" / "x" / "m.trace.json.gz", "wt") as f:
        json.dump({"traceEvents": events}, f)
    loaded = analyze_trace.load_trace(str(d))
    assert analyze_trace.analyze(loaded, "train_step", 10) == out
