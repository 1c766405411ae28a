"""The generator: same seed, same requests and batches; every seed the same
set of sizes in another order."""

import pathlib

from benchmarks import costs, traffic

CFG = costs.load_config("dalle-d12-full")
BACKLOG = traffic.load("backlog-c128", pathlib.Path(__file__).resolve().parent / "data" / "traffic")


def test_same_seed_same_requests_other_seed_other_requests():
    a, b, c = (traffic.RequestStream(BACKLOG, CFG, s) for s in (2**31 + 9, 2**31 + 9, 11))
    ra, rb, rc = ([s.next() for _ in range(5)] for s in (a, b, c))
    assert all((x.prompt == y.prompt).all() and x.seed == y.seed for x, y in zip(ra, rb))
    assert any((x.prompt != y.prompt).any() for x, y in zip(ra, rc))
    for r in ra:
        n = int((r.prompt != 0).sum())
        assert 8 <= n <= 128 and r.prompt.shape == (256,) and r.prompt[:n].min() >= 1
        assert r.prompt.max() < 10000 and r.max_new_tokens == 1024


def test_stagger_is_one_set_in_a_seeded_order():
    a, b = traffic.stagger_budgets(BACKLOG, 1), traffic.stagger_budgets(BACKLOG, 2)
    assert a != b and sorted(a) == sorted(b) == [16 * (i + 1) for i in range(64)]
    assert a == traffic.stagger_budgets(BACKLOG, 1)


def test_samples_per_caption_is_data():
    stream = traffic.RequestStream(dict(BACKLOG, samples_per_caption=4), CFG, 3)
    group = [stream.next() for _ in range(8)]
    assert all((g.prompt == group[0].prompt).all() for g in group[:4])
    assert (group[4].prompt != group[0].prompt).any()
    assert len({g.seed for g in group}) == 8


def test_train_batches_differ_by_step_and_row():
    mix = traffic.load("job-b8-synthimg")
    cfg = costs.load_config("dalle-d12-sparse")
    a, b = traffic.train_batch(mix, cfg, 7, 0), traffic.train_batch(mix, cfg, 7, 1)
    assert a["image"].shape == (8, 256, 256, 3) and a["text"].shape == (8, 256)
    assert (a["image"] != b["image"]).any()
    assert len({row.tobytes() for row in a["text"]}) == 8
    again = traffic.train_batch(mix, cfg, 7, 0)
    assert (a["image"] == again["image"]).all() and (a["text"] == again["text"]).all()
