"""A deployment arrives as files (benchmarks/extend.py): each of the
five kinds is found by name in a directory of its own, a file can add
a name and never replace one, a name that nothing provides is a broken
run before any child exists, and the built-in tables read as they did.
Only the last two tests start a manager."""

import hashlib
import json

import pytest

from benchmarks import (check, control, extend, gen, harness, manifest,
                        roofline, run)

#: kind → (a file of that kind, the name of one built-in)
FILES = {
    "check": ("def check(ctx, rep):\n    pass\n", "acks"),
    "law": ("from benchmarks.gen import ProducerStream\n\n\n"
            "class Stream(ProducerStream):\n    pass\n", "slices"),
    "role": ("class Role:\n    def __init__(self, spec):\n        pass\n",
             "producer"),
    "reduction": ("def reduce(data, p):\n    return 1.0\n", "rate"),
    "kernel": ("def least(data):\n    return {'bytes': 8, 'flops': 0}\n",
               "ewma_scores"),
}
KINDS = sorted(FILES)


@pytest.mark.parametrize("kind", KINDS)
@pytest.mark.parametrize("case", ["found", "builtin", "shadow", "unknown",
                                  "no_attribute", "not_a_name"])
def test_resolver(overlay, kind, case):
    directory, attr = extend.KINDS[kind][:2]
    text, builtin = FILES[kind]
    if case == "found":
        path = overlay.write(f"{directory}/brought.py", text)
        extend.use(overlay.base)
        got = extend.resolve(kind, "brought")
        assert got.__name__ == attr and got.__module__ == \
            f"benchmarks.{directory}.brought"
        assert extend.module(kind, "brought").__file__ == path
    elif case == "builtin":
        extend.use(overlay.base)
        assert extend.module(kind, builtin) is None
        assert extend.resolve(kind, builtin) is extend.builtins(kind)[builtin]
    elif case == "shadow":
        path = overlay.write(f"{directory}/{builtin}.py", text)
        with pytest.raises(extend.RunFailed, match=path):
            extend.use(overlay.base)
    elif case == "unknown":
        extend.use(overlay.base)
        with pytest.raises(extend.RunFailed,
                           match=f"benchmarks/{directory}/nowhere.py"):
            extend.resolve(kind, "nowhere")
    elif case == "no_attribute":
        path = overlay.write(f"{directory}/hollow.py", "x = 1\n")
        extend.use(overlay.base)
        with pytest.raises(extend.RunFailed, match=path):
            extend.resolve(kind, "hollow")
    else:
        with pytest.raises(extend.RunFailed, match="identifier"):
            extend.resolve(kind, "dotted.name")


def test_forgotten_overlay_is_read_anew(overlay):
    for value in (1, 2):
        overlay.write("reduce/brought.py",
                      f"def reduce(data, p):\n    return {value}.0\n"
                      + "# \n" * value)
        extend.use(overlay.base)
        assert extend.resolve("reduction", "brought")({}, {}) == value
        extend.forget(overlay.base)
    with pytest.raises(extend.RunFailed):
        extend.resolve("reduction", "brought")


#: sha256[:16] of values(b), b = 0..7, and of the payloads of blocks 0
#: and 1, of producer 0 as the parent commit's ProducerStream gave them
DIGESTS = {
    ("ingest-saturate", 11): ("8e859ae44ca5f489", "d757f3f7e2362ba7"),
    ("ingest-saturate", 2147483659): ("b5eb563c26ba26e9", "65957ea6dfd37cc2"),
    ("tad-ewma", 11): ("86bc46b4a8662352", "8191d7139752311d"),
    ("tad-ewma", 2147483659): ("76d30878b180b6f8", "161f90a41673996c"),
    ("dashboards-retained", 11): ("37270c2e4f699561", "d8da640c094bfd54"),
    ("dashboards-retained", 2147483659): ("1ae4cba45bff665e",
                                          "edbbd555634f8ed2"),
}


@pytest.mark.parametrize("traffic,seed", sorted(DIGESTS))
def test_stream_factory_gives_the_shipped_rows(traffic, seed):
    stream = gen.stream(manifest.load().traffic(traffic), seed, 0)
    assert type(stream) is gen.ProducerStream
    values, blocks = hashlib.sha256(), hashlib.sha256()
    for b in range(8):
        v = stream.values(b)
        for key in ("conn", "thr", "flow_end"):
            values.update(v[key].tobytes())
    for b in range(2):
        blocks.update(stream.block(b)[0])
    assert (values.hexdigest()[:16], blocks.hexdigest()[:16]) \
        == DIGESTS[traffic, seed]


def test_the_three_users_of_a_stream_share_the_law(overlay, monkeypatch):
    """Producer, check and control get their stream from one factory:
    a law that came as a file reaches all three."""
    overlay.write("laws/brought.py", FILES["law"][0])
    extend.use(overlay.base)
    traffic = overlay.bench.traffic(
        overlay.bench.cell(overlay.cell(generator={"law": "brought"}))
        ["traffic"])
    made = []
    factory = gen.stream
    monkeypatch.setattr(gen, "stream", lambda *a: made.append(
        factory(*a)) or made[-1])
    from benchmarks import client
    spec = {"addr": "http://127.0.0.1:1", "traffic": traffic, "seed": 3,
            "producer": 0, "prepared_blocks": 0}
    client.Producer(spec)
    ctx = {"traffic": traffic, "seed": 3, "specs": [
        {"role": "producer", "producer": 0}],
        "preload": [{"records": []}], "warm": [{"records": []}],
        "results": [{"records": []}], "probes": [{"records": []}]}
    check.streams(ctx)
    control.control_numbers(traffic, 3, 1)
    assert [type(s).__module__ for s in made] \
        == ["benchmarks.laws.brought"] * 6


def test_ewma_scores_roofline_counts_are_unchanged():
    bench = manifest.load()
    data = {"traffic": bench.traffic("tad-ewma"),
            "specs": [{"role": "producer", "preload_blocks": 32},
                      {"role": "jobs"}]}
    assert roofline.series_shape(data) == {"series": 8000, "steps": 128}
    need = roofline.KERNELS["ewma_scores"](data)
    assert need == {"bytes": 8000 * 128 * 10 + 32000, "flops": 0}
    assert roofline.least_seconds("ewma_scores", data,
                                  {"kind": "TPU v5 lite"}) \
        == (8000 * 128 * 10 + 32000) / 819e9


def test_a_kernel_file_is_held_to_the_larger_bound(overlay):
    overlay.write("kernels/brought.py",
                  "def least(data):\n"
                  "    return {'bytes': 819, 'flops': data['flops']}\n")
    extend.use(overlay.base)
    tpu = {"kind": "TPU v5 lite"}
    assert roofline.least_seconds("brought", {"flops": 0}, tpu) == 1e-9
    assert roofline.least_seconds("brought", {"flops": 197e6}, tpu) == 1e-6


def test_control_merges_the_numbers_a_check_file_brings(overlay):
    overlay.write("checks/brought.py", FILES["check"][0]
                  + "limits = ('brought_gap',)\n\n\n"
                  "def control(traffic, seed, n_blocks, precision):\n"
                  "    return {'brought_gap': 0.5 if precision == 'bf16'"
                  " else 0.0}\n")
    extend.use(overlay.base)
    name = overlay.cell(checks=["acks", "detector_alerts", "brought"],
                        limits={"brought_gap": 0.1})
    traffic = overlay.bench.traffic(overlay.bench.cell(name)["traffic"])
    nums = control.control_numbers(traffic, 11, 2)
    assert nums["brought_gap"] == 0.5 and "alert_probe_block_gap" in nums
    assert control.control_numbers(traffic, 11, 2, "f64")["brought_gap"] == 0


def _no_child(monkeypatch):
    def refuse(*args, **kwargs):
        raise AssertionError("a child was started")
    monkeypatch.setattr(harness.subprocess, "Popen", refuse)


BROKEN = {
    "check": dict(checks=["acks", "nowhere"]),
    "law": dict(generator={"law": "nowhere"}),
    "role": dict(workers=[{"role": "producer", "count": 1,
                           "prepared_blocks": 1},
                          {"role": "nowhere"}]),
    "limit": dict(limits={"alert_count_gap": 1e-5}),      # one left out
}


@pytest.mark.parametrize("what", sorted(BROKEN) + ["reduction", "kernel",
                                                   "file_limit", "shadow"])
def test_a_name_nothing_provides_is_a_broken_run(overlay, monkeypatch,
                                                 capsys, what):
    """Exit 1, no result line, the file looked for named on stderr, and
    no manager or worker child started."""
    if what in BROKEN:
        if what == "limit":
            overlay.cell()
            path = overlay.base + "/traffic/overlay-mix.json"
            with open(path) as f:
                traffic = json.load(f)
            traffic["limits"] = BROKEN["limit"]["limits"]
            overlay.write("traffic/overlay-mix.json", json.dumps(traffic))
            names = "alert_probe_block_gap"
        else:
            overlay.cell(**BROKEN[what])
            names = f"benchmarks/{extend.KINDS[what][0]}/nowhere.py"
    elif what == "file_limit":
        overlay.write("checks/brought.py", FILES["check"][0]
                      + "limits = ('brought_gap',)\n")
        overlay.cell(checks=["acks", "brought"])
        names = "brought_gap"
    elif what == "shadow":
        overlay.cell()
        names = overlay.write("checks/acks.py", FILES["check"][0])
    else:
        overlay.cell()
        reader = {"reduce": "nowhere"} if what == "reduction" else \
            {"reduce": "roofline_share", "kernel": "nowhere", "match": "x"}
        overlay.write("layer_metrics/brought.json", json.dumps(reader))
        overlay.doc["per_layer"].append({
            "name": "brought", "unit": "%", "better": "higher",
            "source": "device_trace", "layer": "detector",
            "moves": "acked_rows_per_s", "workloads": [overlay.name]})
        names = (f"benchmarks/{extend.KINDS[what][0]}/nowhere.py")
    monkeypatch.setattr(manifest, "load", lambda path=None: overlay.bench)
    _no_child(monkeypatch)
    rc = run.main(["--workload", overlay.name, "--seed", "2147483659",
                   "--seconds", "3", "--trace", "0"])
    io = capsys.readouterr()
    assert rc == 1
    assert "run failed" in io.err and names in io.err
    assert not [ln for ln in io.out.splitlines() if ln.startswith("{")]


GAP_CHECK = '''from benchmarks import check as _check

limits = ("rows_beyond",)


def check(ctx, rep):
    want = sum(n * stream.rows for stream, n, _ in _check.streams(ctx))
    got = ctx["health"]["store"]["flowRows"]
    rep.compare("rows_beyond", abs(got - want - BESIDE) / max(want, 1),
                _check.limit(ctx["traffic"], "rows_beyond"), f"{got} rows, {want} sent")
'''


@pytest.mark.parametrize("beside,correct", [(0, True), (1, False)])
def test_a_check_file_decides_correct(overlay, capsys, beside, correct):
    """A check that came as a file is run with the built-in ones, its
    number stands beside its limit in the result, and a gap it finds
    gives `correct: false`."""
    from benchmarks import rehearsal
    import time
    overlay.write("checks/rows_held.py", GAP_CHECK + f"\n\nBESIDE = {beside}\n")
    name = overlay.cell(checks=["acks", "store_totals", "rows_held"],
                        limits={"rows_beyond": 0.0})
    bench = overlay.bench
    out = harness.run_cell(name, 7, 3.0, False, time.monotonic(),
                           platform="cpu", bench=bench,
                           scale=rehearsal.scale_for(bench, bench.cell(name)))
    assert out["correct"] is correct
    assert list(out)[-1] == "checks"
    assert out["checks"]["rows_beyond"]["limit"] == 0.0
    assert (out["checks"]["rows_beyond"]["value"] == 0) is correct
    failed = [ln.split()[1].rstrip(":") for ln in
              capsys.readouterr().out.splitlines()
              if ln.startswith("check ") and "FAILED" in ln]
    assert failed == ([] if correct else ["rows_beyond"])
