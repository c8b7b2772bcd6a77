"""The generator: the same seed gives the same requests, every seed the
same work in another order, and the mix's stated parameters hold."""
import numpy as np
import pytest

from harness import cells, traffic

BIG = 2**40 + 12345          # seeds past 32 bits are fine


def chat():
    return cells.load_traffic("chat")


def test_deterministic_per_seed():
    a = traffic.serve_requests(chat(), BIG, 30, 152064)
    b = traffic.serve_requests(chat(), BIG, 30, 152064)
    assert [(r.due, r.tenant, r.max_new) for r in a] == \
        [(r.due, r.tenant, r.max_new) for r in b]
    assert all(np.array_equal(x.prompt, y.prompt) for x, y in zip(a, b))


@pytest.mark.parametrize("mix", ["chat", "docqa"])
def test_same_work_every_seed(mix):
    m = cells.load_traffic(mix)
    runs = [traffic.serve_requests(m, s, 40, 152064) for s in (1, 2, BIG)]
    n = round(m["rate_per_s"] * 40)
    for reqs in runs:
        assert len(reqs) == n
        assert all(0 <= r.due < 40 for r in reqs)
    key = lambda rs: (sum(len(r.prompt) for r in rs),
                      sorted(len(r.prompt) - r.prefix for r in rs),
                      max(r.prefix for r in rs),
                      sorted(r.max_new for r in rs))
    assert key(runs[0]) == key(runs[1]) == key(runs[2])
    dues = [sorted(r.due for r in rs) for rs in runs]
    assert dues[0] == dues[1] == dues[2]                # same arrivals
    assert [r.max_new for r in runs[0]] != [r.max_new for r in runs[1]]


def test_chat_lengths_and_zipf():
    m = chat()
    reqs = traffic.serve_requests(m, 7, 200, 152064)
    user = np.array([len(r.prompt) - 512 for r in reqs])
    out = np.array([r.max_new for r in reqs])
    assert user.min() >= 128 and user.max() <= 4096
    assert abs(np.median(user) - 1024) / 1024 < 0.05
    assert out.min() >= 32 and out.max() <= 512
    assert abs(np.median(out) - 192) / 192 < 0.05
    assert all(r.prefix == 512 for r in reqs)
    counts = np.bincount(traffic.zipf_tenants(1000, 64, 1.0), minlength=64)
    assert counts.sum() == 1000
    assert counts[0] / counts[9] == pytest.approx(10, rel=0.15)
    assert all(0 <= t < 152064 for r in reqs for t in r.prompt[:50])


def test_chat_tail_tenants_come_in_a_short_window():
    """Tenants past the bank's resident slots get their share of sessions
    in a window as short as a run's, so their adapters page in."""
    m = chat()
    reqs = traffic.serve_requests(m, 11, 51, 152064)
    rank = {n: i for i, n in enumerate(traffic.tenant_names(m))}
    tail = sum(1 for r in reqs if rank[r.tenant] >= m["resident_slots"])
    p = 1.0 / np.arange(1, 65)
    share = p[m["resident_slots"]:].sum() / p.sum()
    assert tail == pytest.approx(share * len(reqs), abs=1)


def test_docqa_sessions_share_documents():
    m = cells.load_traffic("docqa")
    reqs = traffic.serve_requests(m, 3, 40, 152064)
    by_session = {}
    for r in reqs:
        by_session.setdefault(r.session, []).append(r)
    assert max(len(v) for v in by_session.values()) == 4
    for asks in by_session.values():
        doc = asks[0].prompt[:asks[0].prefix]
        assert 2048 <= len(doc) <= 8192
        assert len({r.tenant for r in asks}) == 1
        assert all(np.array_equal(r.prompt[:r.prefix], doc) for r in asks)
    assert traffic.max_len(m, 16) == 8448
    assert traffic.max_len(chat(), 16) == 5120


def test_train_rows_differ_by_stream():
    m = cells.load_traffic("gsoft-train")
    a = traffic.train_rows(m, BIG, 1, 152064, 0)
    b = traffic.train_rows(m, BIG, 1, 152064, 1)
    assert a["tokens"].shape == (1, 2048)
    assert np.array_equal(a["tokens"][:, 1:], a["labels"][:, :-1])
    assert not np.array_equal(a["tokens"], b["tokens"])
