"""The reference's sample covers a bank slot paged in during the window
wherever the window paged one in; where no finished request was admitted
after a page-in, the run counts as unchecked."""
import numpy as np

from harness import serve, traffic
from harness.probe import Probe

T0 = 100.0


def requests(n=8):
    return [traffic.Request(idx=i, due=float(i), tenant=f"tenant{i % 4:03d}",
                            prompt=np.arange(30 - i, dtype=np.int32),
                            max_new=4, prefix=0, session=i,
                            prefix_key=f"t{i % 4}")
            for i in range(n)]


def probe_with(page_ins, chunk0):
    p = Probe()
    p.page_ins = page_ins
    p.t_chunk0 = chunk0
    return p


def test_sample_takes_a_request_after_a_page_in():
    reqs = requests()
    outputs = {r.idx: [1, 2, 3, 4] for r in reqs}
    # tenant003 paged in at set-up (ignored) and again in the window
    page_ins = [("tenant003", T0 - 5), ("tenant003", T0 + 6.5)]
    chunk0 = {r.idx: T0 + r.due + 0.1 for r in reqs}
    sample, covered = serve.pick_sample(reqs, probe_with(page_ins, chunk0),
                                        outputs, 7, T0)
    assert covered
    # the longest first, then tenant003's first request admitted after
    # the page-in at 6.5 s (its request due at 3 s was admitted before)
    assert [r.idx for r in sample[:2]] == [0, 7]


def test_page_in_without_a_finished_request_is_unchecked():
    reqs = requests()
    outputs = {r.idx: [1, 2, 3, 4] for r in reqs if r.tenant != "tenant003"}
    page_ins = [("tenant003", T0 + 2.5)]
    chunk0 = {r.idx: T0 + r.due + 0.1 for r in reqs}
    _, covered = serve.pick_sample(reqs, probe_with(page_ins, chunk0),
                                   outputs, 7, T0)
    assert not covered


def test_no_page_in_needs_none():
    reqs = requests()
    outputs = {r.idx: [1, 2, 3, 4] for r in reqs}
    _, covered = serve.pick_sample(reqs, probe_with([("tenant001", T0 - 1)],
                                                    {}), outputs, 7, T0)
    assert covered
