"""The yardstick's arithmetic, on hand-made inputs."""

import collections
import json

import pytest

import bench_paths  # noqa: F401
from benchmark.harness import cells, peaks, probe, prom, stats, traffic
from benchmark.harness.loadgen import Record
from benchmark.harness.rundata import RunData
from benchmark.harness.server import BenchFailure, device_of

MIX = {
    "kind": "open_poisson", "params": {"rate": 5.0},
    "prompt_tokens": {"median": 40, "sigma": 0.8, "min": 8, "max": 120},
    "output_tokens": {"median": 12, "sigma": 0.6, "min": 4, "max": 32},
    "temperature": 0.7, "pool_seed": 9,
}


def lengths(requests):
    return collections.Counter((len(r.prompt), r.max_tokens) for r in requests)


def test_same_seed_same_traffic_other_seed_same_sizes_other_contents():
    kind = cells.load_module("traffic_kinds", "open_poisson")
    n = kind.count(MIX["params"], 10.0)
    a = traffic.requests_for(MIX, n, 2**31 + 5, 512)
    b = traffic.requests_for(MIX, n, 2**31 + 5, 512)
    c = traffic.requests_for(MIX, n, 6, 512)
    assert n == 50 and a == b
    assert [r.prompt for r in a] != [r.prompt for r in c]
    assert [r.seed for r in a] != [r.seed for r in c]
    # the seed changes the contents, never the sizes or their order
    sizes = lambda reqs: [(len(r.prompt), r.max_tokens) for r in reqs]  # noqa: E731
    assert sizes(a) == sizes(c) and len(set(sizes(a))) > 20
    assert sizes(a) != sizes(traffic.requests_for(dict(MIX, pool_seed=10), n, 6, 512))
    assert all(8 <= len(r.prompt) <= 120 and 4 <= r.max_tokens <= 32 for r in a)
    assert not any(t in traffic.RESERVED or t < 3 for r in a for t in r.prompt)
    due = kind.due_times(dict(MIX["params"], pool_seed=9), n, 10.0)
    assert due == kind.due_times(dict(MIX["params"], pool_seed=9), n, 10.0)
    assert due != kind.due_times(dict(MIX["params"], pool_seed=8), n, 10.0)
    assert len(due) == n and due == sorted(due) and 0 < due[0] and due[-1] < 10.0
    gaps = [b - a for a, b in zip([0.0] + due, due)]
    assert max(gaps) > 3 * (10.0 / n) > 60 * min(gaps)  # exponential, not even


def test_shared_prefix_is_shared_by_the_stated_share():
    mix = dict(MIX, shared_prefix={"groups": 2, "tokens": 16, "share": 0.75})
    requests = traffic.requests_for(mix, 200, 1, 512)
    heads = collections.Counter(r.prompt[:7] for r in requests)
    shared = sum(n for n in heads.values() if n > 1)
    assert len([h for h, n in heads.items() if n > 1]) == 2
    assert 0.6 < shared / 200 < 0.9


def record(due, tokens, finish="length", asked=None, error=None, gave_up=None):
    return Record(
        index=0, prompt_tokens=8, asked_tokens=asked or len(tokens), due_s=due,
        sent_s=due + 0.001, token_s=list(tokens), finish_reason=finish,
        done_s=None if error else (tokens[-1] if tokens else due),
        error=error, gave_up_s=gave_up,
    )


def test_percentiles_ttft_and_tpot_on_hand_made_timestamps():
    assert stats.percentile([1, 2, 3, 4, 5], 50) == 3
    assert stats.percentile(list(range(1, 101)), 95) == pytest.approx(95.05)
    with pytest.raises(ValueError):
        stats.percentile([], 50)
    # Tokens arrive in groups of the decode window: first token at 0.2 s,
    # then 8 at 0.5 s, then 8 at 0.8 s. Per request, not per gap.
    grouped = record(0.1, [0.2] + [0.5] * 8 + [0.8] * 8)
    assert stats.ttft_ms(grouped) == pytest.approx(100.0)
    assert stats.tpot_ms(grouped) == pytest.approx(600.0 / 16)
    assert stats.tpot_ms(record(0.0, [0.3])) is None  # one token: no gap
    # A request that never answered counts until the harness gave up.
    lost = record(1.0, [], error="refused", gave_up=31.0)
    assert not lost.ok and stats.ttft_ms(lost) == pytest.approx(30000.0)
    early_stop = record(0.0, [0.1, 0.2], finish="stop", asked=5)
    short = record(0.0, [0.1, 0.2], finish="length", asked=5)
    assert early_stop.ok and not short.ok
    records = [grouped, lost, early_stop, short]
    assert stats.tpot_samples(records) == [
        pytest.approx(37.5), pytest.approx(100.0),
    ]
    value, n = stats.end_to_end("ttft_p95_ms", records, 1.0)
    assert n == 4 and value > 20000  # the failure makes the tail worse
    assert stats.end_to_end("tpot_p50_ms", records, 1.0) == (pytest.approx(68.75), 2)
    rate, tokens = stats.end_to_end("out_tok_per_s", records, 0.5)
    assert tokens == 9 + 2 + 2 and rate == pytest.approx(26.0)
    for unknown in ("setup_s", "ttft_mean_ms", "ttft_p95_s", "itl_p50_ms"):
        with pytest.raises(KeyError):
            stats.end_to_end(unknown, records, 1.0)


@pytest.mark.parametrize("q,n,ok", [
    (95, 199, False), (95, 200, True), (90, 100, True), (90, 99, False),
    (50, 20, True),
])
def test_a_percentile_wants_ten_samples_beyond_it(q, n, ok):
    assert stats.tail_supported(n, q) is ok
    assert stats.quantile_of(f"ttft_p{q}_ms") == q
    assert stats.quantile_of("out_tok_per_s") is None


START = """# HELP app_tpu_queue_wait_seconds wait
app_tpu_queue_wait_seconds_sum{model="m"} 1.5
app_tpu_queue_wait_seconds_count{model="m"} 10
app_tpu_tokens_generated{model="m"} 100.0
app_tpu_batch_occupancy{model="m"} 0.25
"""
END = """app_tpu_queue_wait_seconds_sum{model="m"} 4.5
app_tpu_queue_wait_seconds_count{model="m"} 40
app_tpu_tokens_generated{model="m"} 700.0
app_tpu_steady_state_recompiles_total{model="m",program="spec_window"} 2.0
app_tpu_batch_occupancy{model="m"} 0.75
"""


def run_data(**over):
    base = dict(
        seconds=10.0, records=[], prom_start=prom.parse(START),
        prom_end=prom.parse(END), prom_samples=[], endpoints={}, trace=None,
    )
    return RunData(**{**base, **over})


def test_prom_readers_take_window_deltas_and_sample_means():
    delta = cells.load_module("readers", "prom_delta")
    run = run_data(prom_samples=[prom.parse(START), prom.parse(END), {}])
    assert delta.read(run, "app_tpu_queue_wait_seconds", histogram=True,
                      scale=1000.0) == pytest.approx(100.0)
    assert delta.read(run, "app_tpu_tokens_generated") == 600.0
    # A counter exported only after its first increment starts from 0.
    assert delta.read(run, "app_tpu_steady_state_recompiles_total") == 2.0
    assert delta.read(run, "app_tpu_never_observed", histogram=True) is None
    sampled = cells.load_module("readers", "prom_sampled")
    assert sampled.read(run, "app_tpu_batch_occupancy") == pytest.approx(0.5)
    assert sampled.read(run, "app_tpu_missing") is None


def test_json_path_and_client_lag_readers():
    path = cells.load_module("readers", "json_path")
    run = run_data(endpoints={
        "debug_loop": {"host_overhead_ratio": 0.4},
        "health": {"hbm": [{"peak_bytes_in_use": 3e9},
                           {"peak_bytes_in_use": 5e9}]},
    })
    assert path.read(run, "debug_loop", ["host_overhead_ratio"]) == 0.4
    assert path.read(run, "health", ["hbm", "*", "peak_bytes_in_use"],
                     reduce="max", scale=1e-9) == pytest.approx(5.0)
    assert path.read(run, "health", ["no", "such"]) is None  # the CPU
    lag = cells.load_module("readers", "client_lag")
    late = [record(float(i), [i + 0.5]) for i in range(10)]
    assert lag.read(run_data(records=late), q=95) == pytest.approx(1.0)
    assert lag.read(run_data(), q=95) is None


def test_client_rate_and_client_gap_readers():
    rate = cells.load_module("readers", "client_rate")
    gap = cells.load_module("readers", "client_gap")
    # One stream stood still for 7 s between two groups; the other did not.
    frozen = record(0.0, [1.0] + [1.5] * 8 + [8.5] * 8)
    steady = record(0.0, [0.5] + [1.0] * 8 + [11.0])  # last token: too late
    run = run_data(records=[frozen, steady])
    # all the tokens stamped inside the 10 s, over all of them
    assert rate.read(run) == pytest.approx((17 + 9) / 10.0)
    assert gap.read(run) == pytest.approx(10000.0)  # time to first token is no gap
    assert gap.read(run_data(records=[frozen])) == pytest.approx(7000.0)
    assert rate.read(run_data()) is None and gap.read(run_data()) is None
    assert gap.read(run_data(records=[record(0.0, [0.3])])) is None


def test_peaks_table_refuses_an_unknown_device():
    assert peaks.device_peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError, match="no published peaks"):
        peaks.device_peaks("cpu")


class FakeServer:
    def __init__(self, platform):
        self.platform = platform

    def get_json(self, path, **kw):
        return {"platform": self.platform, "kind": "x", "count": 1}

    def tpu_health(self):
        return {"details": {"platform": self.platform}}


def test_a_run_off_the_chip_ends_before_any_result():
    assert device_of(FakeServer("tpu"), 1, "tpu")["count"] == 1
    with pytest.raises(BenchFailure, match="need 1 tpu"):
        device_of(FakeServer("cpu"), 1, "tpu")
    with pytest.raises(BenchFailure, match="need 4 tpu"):
        device_of(FakeServer("tpu"), 4, "tpu")


class ProbedServer:
    """Serves -1.0 for every token; its reference says ``reads`` for the
    plain mathematics and for each ablation (None: it changes nothing at
    this length)."""

    def __init__(self, reads):
        self.reads = reads

    def get_json(self, path, **kw):
        assert path == "/bench/reference"
        return {"ablations": [name for name in self.reads if name]}

    def post_json(self, path, body, **kw):
        if path == "/bench/reference":
            read = self.reads[body["ablate"]]
            return {"logprobs": [
                None if read is None else [read] * (len(seq) - body["n_prompt"])
                for seq in body["sequences"]
            ]}
        n = body["max_tokens"]
        return {"choices": [{"logprobs": {"token_logprobs": [-1.0] * n}}]}

    def request(self, method, path, body=None, **kw):
        chunk = {"choices": [{"token_ids": [7] * body["max_tokens"]}]}
        return 200, f"data: {json.dumps(chunk)}\n\ndata: [DONE]\n".encode()


@pytest.mark.parametrize("reads,agrees,ablated", [
    # the reference agrees and each piece that applies is caught
    ({"": -1.01, "mask": -4.0, "gate": None}, True, {"mask"}),
    # the configuration's own ablation is asked for by the name it gave,
    # and one that still agrees fails the probe
    ({"": -1.01, "mask": -4.0, "gate": -1.02}, False, {"mask", "gate"}),
    # no piece applied: the comparison showed no teeth
    ({"": -1.01, "mask": None, "gate": None}, False, set()),
    ({"": -3.0, "mask": -4.0}, False, {"mask"}),
])
def test_probe_asks_for_the_references_own_ablations(reads, agrees, ablated):
    found = probe.probe_reference(ProbedServer(reads), {}, 2**31 + 3, 512)
    assert found["agrees"] is agrees and set(found["ablated"]) == ablated
    assert found["sequences"] == probe.PROBES
    numbers = probe.compared(found)
    assert numbers["ablations_applied"]["value"] == len(ablated)
    assert numbers["probe_median_nats"]["limit"] == probe.MEDIAN_TOLERANCE
    assert {f"ablated_{a}_median_nats" for a in ablated} <= set(numbers)
