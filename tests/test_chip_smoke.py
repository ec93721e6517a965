"""chip_smoke.py rehearsed on the CPU at a tiny size: every phase passes,
then the script refuses the platform. Each run works in its own copy of
the files the script needs (the script rebuilds the C++ parser from
source, which must not race the other workers' loaded library)."""

import json
import os
import shutil
import signal
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PHASES = ["build", "start", "ingest", "query_groupby_1_1_1",
          "query_downsample_all_hosts", "query_lastpoint", "query_wide_values",
          "compact", "kernels"]


@pytest.fixture()
def checkout(tmp_path):
    root = tmp_path / "checkout"
    shutil.copytree(
        os.path.join(REPO, "horaedb_tpu"), root / "horaedb_tpu",
        ignore=shutil.ignore_patterns("__pycache__", "*.so"),
    )
    (root / "docs").mkdir()
    shutil.copy(os.path.join(REPO, "docs", "example.toml"), root / "docs")
    shutil.copy(os.path.join(REPO, "chip_smoke.py"), root)
    return root


def _start(checkout, tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "jax_cache"))
    env.pop("PYTHONPATH", None)
    return subprocess.Popen(
        [sys.executable, "chip_smoke.py", "--hosts", "20", "--hours", "0.5",
         "--report-dir", str(tmp_path / "report")],
        cwd=checkout, env=env, stdout=subprocess.PIPE, stderr=subprocess.PIPE,
        text=True,
    )


def _lines(stdout: str) -> list[dict]:
    return [json.loads(line) for line in stdout.splitlines() if line.strip()]


def test_rehearsal_passes_every_phase_then_refuses_the_cpu(checkout, tmp_path):
    proc = _start(checkout, tmp_path)
    out, err = proc.communicate(timeout=170)
    lines = _lines(out)
    assert proc.returncode != 0, out
    assert out.splitlines()[-1] == '{"ok": false}'
    by_phase = {ln["phase"]: ln for ln in lines[:-1]}
    for name in PHASES:
        assert by_phase[name]["ok"] is True, (name, out, err[-2000:])
    start = by_phase["start"]
    assert start["parser_backend"] == "native"
    assert start["platform"] == "cpu"
    # the variable reached the child unchanged, and nothing else was set
    assert start["compile_cache_dir"] == str(tmp_path / "jax_cache")
    assert by_phase["ingest"]["samples"] == 20 * 10 * 180
    assert "platform 'cpu'" in by_phase["failed"]["error"]
    assert os.path.exists(checkout / "horaedb_tpu" / "native" / "libremote_write.so")
    assert os.path.exists(tmp_path / "report" / "kernels.json")


def test_a_killed_child_ends_the_run_with_ok_false(checkout, tmp_path):
    proc = _start(checkout, tmp_path)
    try:
        # the "start" line means the child serves; kill it under the parent
        seen = []
        while True:
            line = proc.stdout.readline()
            assert line, ("".join(seen), proc.stderr.read()[-2000:])
            seen.append(line)
            if json.loads(line).get("phase") == "start":
                break
        with open(f"/proc/{proc.pid}/task/{proc.pid}/children") as f:
            children = [int(p) for p in f.read().split()]
        assert len(children) == 1, children
        os.kill(children[0], signal.SIGKILL)
        rest, _ = proc.communicate(timeout=120)
    finally:
        if proc.poll() is None:
            proc.kill()
    lines = _lines("".join(seen) + rest)
    assert proc.returncode != 0
    assert lines[-1] == {"ok": False}
    failed = [ln for ln in lines if ln.get("phase") == "failed"]
    assert failed and failed[0]["ok"] is False


# -- the script's own pieces, no server ---------------------------------------


@pytest.fixture(scope="module")
def smoke():
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(REPO, "chip_smoke.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_request_template_is_what_the_protobuf_runtime_reads(smoke):
    """The hand-written wire encoder against the generated protobuf
    classes: labels, order, the one sample of each series."""
    from horaedb_tpu.pb import remote_write_pb2

    tags, values = smoke.make_fleet(3, 4, 2)
    req = remote_write_pb2.WriteRequest()
    req.ParseFromString(smoke.RequestTemplate(tags).fill(values[:, :, 1], smoke.BASE_MS))
    assert len(req.timeseries) == 4 * len(smoke.CPU_FIELDS)
    for i, series in enumerate(req.timeseries):
        h, f = divmod(i, len(smoke.CPU_FIELDS))
        labels = {lb.name.decode(): lb.value.decode() for lb in series.labels}
        assert labels == {"__name__": f"cpu_{smoke.CPU_FIELDS[f]}", **tags[h]}
        assert [lb.name for lb in series.labels] == sorted(lb.name for lb in series.labels)
        assert [(s.timestamp, s.value) for s in series.samples] == \
            [(smoke.BASE_MS, float(values[f, h, 1]))]


def test_a_template_is_refilled_in_place(smoke):
    tags, values = smoke.make_fleet(0, 2, 2)
    tmpl = smoke.RequestTemplate(tags)
    first = tmpl.fill(values[:, :, 0], smoke.BASE_MS)
    second = tmpl.fill(values[:, :, 1], smoke.BASE_MS + smoke.SCRAPE_MS)
    assert len(first) == len(second) and first != second
    assert tmpl.fill(values[:, :, 0], smoke.BASE_MS) == first


def test_fleet_is_a_function_of_the_seed(smoke):
    import numpy as np

    a, b, c = (smoke.make_fleet(s, 5, 8) for s in (1, 1, 2))
    assert a[0] == b[0] and np.array_equal(a[1], b[1])
    assert not np.array_equal(a[1], c[1])
    assert a[1].shape == (len(smoke.CPU_FIELDS), 5, 8)
    assert a[1].min() >= 0.0 and a[1].max() <= 100.0
    assert set(a[0][0]) == {"hostname", "region", "datacenter", "rack", "os", "arch",
                            "team", "service", "service_version", "service_environment"}


def test_reference_windows_are_right_aligned_half_open(smoke):
    """[t - step, t), the engine's documented window (promql/eval.py): a
    sample at exactly t belongs to the NEXT step; empty windows read NaN."""
    import numpy as np

    ts = np.array([0, 100_000, 299_999, 300_000, 600_000])
    vals = np.array([[1.0, 5.0, 3.0, 9.0, 2.0]])
    steps = np.array([300_000, 600_000, 900_000, 1_200_000])
    out = smoke.window_reduce(vals, ts, steps, np.max)
    assert out.shape == (1, 4)
    assert out[0, :3].tolist() == [5.0, 9.0, 2.0] and np.isnan(out[0, 3])


def _matrix(host, points):
    return {host: [[t / 1000.0, repr(v)] for t, v in points]}


@pytest.mark.parametrize("case", ["equal", "value_off", "bucket_missing",
                                  "host_missing", "within_tolerance"])
def test_compare_holds_answers_to_the_reference(smoke, case):
    import numpy as np

    steps = np.array([1000, 2000, 3000], dtype=np.int64)
    want = np.array([[1.5, np.nan, 2.5]])
    points = [(1000, 1.5), (3000, 2.5)]
    exact = True
    if case == "value_off":
        points = [(1000, 1.5), (3000, 2.5000001)]
    elif case == "bucket_missing":
        points = [(1000, 1.5)]
    elif case == "within_tolerance":
        points, exact = [(1000, 1.5), (3000, 2.5 * (1 + 1e-12))], False
    got = _matrix("host_7" if case != "host_missing" else "host_8", points)
    if case in ("equal", "within_tolerance"):
        smoke.compare(got, want, [7], steps, exact, case)
    else:
        with pytest.raises(smoke.Failed):
            smoke.compare(got, want, [7], steps, exact, case)


def test_bench_refuses_to_measure_off_the_tpu():
    """bench.py without --smoke on the CPU exits non-zero before measuring
    and starts no child of itself."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run([sys.executable, os.path.join(REPO, "bench.py")],
                          env=env, capture_output=True, text=True, timeout=120)
    assert proc.returncode != 0
    assert "measures on the TPU" in proc.stderr
    assert proc.stdout.strip() == ""
    with open(os.path.join(REPO, "bench.py"), encoding="utf-8") as f:
        assert "subprocess" not in f.read().split("def main()")[1]
