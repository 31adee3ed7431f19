"""The waiting-queue stall: with no host KV space and nothing running, two
waiting requests holding device context (a resumed preserved context at
the FCFS head and a partial chunked prefill behind it) fill the pool.
Step 2's eviction needs a running request, so before the repair no step
ran again; now the later holder is discarded to recompute debt."""
import copy

from repro.configs import get_config
from repro.core import POLICIES, Scheduler
from repro.core.request import Interception, Phase, Request, Segment
from repro.serving.engine import Engine

from test_scheduler import _cost, _req


def _requests():
    # a pauses on a near-instant tool, so min-waste preserves its 204
    # tokens; b's prefill takes the rest of a 256-token pool meanwhile
    a = Request(rid=0, arrival=0.0, prompt_len=200,
                segments=[Segment(4, Interception("math", 9e-5, 10)),
                          Segment(4, None)])
    b = Request(rid=1, arrival=0.0, prompt_len=200,
                segments=[Segment(4, None)])
    return [a, b]


def _serve(n_pages, monkeypatch=None):
    cfg = get_config("llama3.2-1b", tiny=True)
    eng = Engine(cfg, POLICIES["infercept"], page_size=16, n_pages=n_pages,
                 max_model_len=256, seed=0)
    eng.sched.cpu_capacity = 0                 # host_kv_tokens 0
    if monkeypatch is not None:
        monkeypatch.setattr(eng.sched, "_evict_for_stalled_head",
                            lambda *a: 0)
    reqs = _requests()
    for r in copy.deepcopy(reqs):
        eng.add_request(r)
    fin = eng.run(max_steps=400)
    return eng, {r.rid: eng.generated_text(r) for r in fin}


def test_stalled_head_evicts_waiting_holders(monkeypatch):
    # 24 pages: (24 - 8) x 16 = 256 tokens of capacity
    eng, streams = _serve(24)
    assert sorted(streams) == [0, 1], "a step stopped running"
    assert eng.sched.stats.stall_evictions > 0
    assert eng.sched.stats.recompute_tokens > 0
    # the evicted prefill is recomputed into the same greedy stream an
    # undisturbed run (a pool that never fills) serves
    roomy, undisturbed = _serve(64)
    assert roomy.sched.stats.stall_evictions == 0
    assert streams == undisturbed
    # without the repair the same traffic stalls: nothing finishes
    stuck, none = _serve(24, monkeypatch)
    assert none == {} and stuck.sched.waiting


def test_stalled_head_evicts_latest_holder_first():
    sched = Scheduler(POLICIES["infercept"], _cost(),
                      gpu_capacity_tokens=120)
    head = _req(1, prompt=100, arrival=0.0)
    mid = _req(2, prompt=90, arrival=1.0)
    late = _req(3, prompt=90, arrival=2.0)
    for r in (head, mid, late):
        sched.submit(r)
    head.device_tokens = 60                # a partial prefill, 40 to go
    mid.device_tokens, late.device_tokens = 30, 30   # pool now full
    plan = sched.next_iteration(3.0)
    # one eviction frees room: the latest arrival goes, mid keeps its pages
    assert sched.stats.stall_evictions == 1
    assert late.device_tokens == 0 and late in sched.waiting
    assert mid.device_tokens == 30
    assert plan.chunks == [(head, 30)]


def test_head_that_fits_evicts_nothing():
    """Nothing runs, but the head fits (a chunk of the free pool): the
    holder behind it keeps its partial prefill."""
    sched = Scheduler(POLICIES["infercept"], _cost(),
                      gpu_capacity_tokens=120)
    head = _req(1, prompt=60, arrival=0.0)
    later = _req(2, prompt=90, arrival=1.0)
    sched.submit(head)
    sched.submit(later)
    later.device_tokens = 50
    plan = sched.next_iteration(2.0)
    assert sched.stats.stall_evictions == 0
    assert later.device_tokens == 50 and later.phase == Phase.WAITING
    assert plan.chunks[0] == (head, 60)
