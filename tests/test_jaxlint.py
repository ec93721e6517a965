"""The JAX-aware lint gate (tools/jaxlint.py) stays SHARP: every rule
fires on a seeded defect and the accepted idioms of this codebase do
not trip it. The tree-is-clean enforcement lives in tests/test_lint.py
(one full-tree pass per pytest session, both analyzers)."""

import subprocess
import sys
from pathlib import Path

REPO = Path(__file__).resolve().parent.parent


def run_jaxlint(*args, cwd=REPO):
    return subprocess.run(
        [sys.executable, "-m", "tools.jaxlint", *map(str, args)],
        capture_output=True, text=True, cwd=cwd, timeout=120,
    )


def hot_file(tmp_path, text):
    """Seed a corpus file under a synthetic hot-module path so the
    path-scoped rules (J001 hot-module, J003 engine-code) apply — the
    same way they do to the real horaedb_tpu/ops/ tree."""
    d = tmp_path / "horaedb_tpu" / "ops"
    d.mkdir(parents=True, exist_ok=True)
    f = d / "seeded.py"
    f.write_text(text)
    return f


class TestJaxlintGate:
    def test_every_rule_fires_on_seeded_defects(self, tmp_path):
        """One defect per rule; the gate is only worth trusting if each
        actually fires (acceptance: J001..J004 on a seeded file)."""
        bad = hot_file(
            tmp_path,
            "import threading\n"
            "import time\n"
            "import jax\n"
            "import jax.numpy as jnp\n"
            "import numpy as np\n"
            "\n"
            "@jax.jit\n"
            "def kernel(x):\n"
            "    v = float(x)\n"                     # J001 concretize
            "    np.asarray(x)\n"                    # J001 host sync
            "    print('trace', x)\n"                # J002 trace-time only
            "    t = time.time()\n"                  # J002 frozen
            "    return v + t\n"
            "\n"
            "g = jax.jit(lambda y: y.sum())\n"
            "def call_site(x):\n"
            "    return g('fast')\n"                 # J002 untraceable str
            "\n"
            "def dtype_drift():\n"
            "    return jnp.array([1.0]), jnp.full((4,), 0.5)\n"  # J003 x2
            "\n"
            "def host_sync(x):\n"
            "    return x.item()\n"                  # J001 hot module
            "\n"
            "class Cache:\n"
            "    def __init__(self):\n"
            "        self._lock = threading.Lock()\n"
            "        self._items = {}\n"
            "    def drop(self, k):\n"
            "        with self._lock:\n"
            "            self._items.pop(k, None)\n"  # declares _items guarded
            "    def put(self, k, v):\n"
            "        self._items[k] = v\n"           # J004 outside lock
        )
        r = run_jaxlint(bad)
        assert r.returncode != 0
        for code in ("J001", "J002", "J003", "J004"):
            assert code in r.stdout, (code, r.stdout)
        # clickable path:line: CODE shape (satellite: CI-friendly output)
        assert f"{bad}:9: J001" in r.stdout, r.stdout

    def test_j005_timer_inside_jit_fires(self, tmp_path):
        """scanstats.stage()/tracing spans opened inside a jit body time
        the trace, not the kernel — J005, with the aliased and bare-import
        forms covered."""
        bad = hot_file(
            tmp_path,
            "import jax\n"
            "from horaedb_tpu.common import tracing\n"
            "from horaedb_tpu.storage import scanstats\n"
            "from horaedb_tpu.storage.scanstats import stage\n"
            "\n"
            "@jax.jit\n"
            "def kernel(x):\n"
            "    with scanstats.stage('kernel'):\n"      # J005 dotted
            "        y = x.sum()\n"
            "    with tracing.span('merge'):\n"          # J005 tracing
            "        y = y + 1\n"
            "    with stage('again'):\n"                 # J005 bare import
            "        return y\n"
        )
        r = run_jaxlint(bad)
        assert r.returncode != 0
        assert r.stdout.count("J005") == 3, r.stdout
        assert f"{bad}:8: J005" in r.stdout, r.stdout

    def test_j005_host_side_timers_pass(self, tmp_path):
        """Timers at the kernel call boundary (host side) are the accepted
        idiom — the rule must not fire on how the tree actually times
        kernels, and a reasoned suppression works."""
        ok = hot_file(
            tmp_path,
            "import jax\n"
            "from horaedb_tpu.common import tracing\n"
            "from horaedb_tpu.common.xprof import xjit\n"
            "from horaedb_tpu.storage import scanstats\n"
            "\n"
            "@xjit(kernel='k')\n"
            "def kernel(x):\n"
            "    return x.sum()\n"
            "\n"
            "def run(x):\n"
            "    with scanstats.stage('device_merge'):\n"
            "        out = kernel(x)\n"
            "    with tracing.span('collect'):\n"
            "        return out\n"
            "\n"
            "@xjit(kernel='s')\n"
            "def suppressed(x):\n"
            "    # jaxlint: disable=J005 measured: trace-time probe only\n"
            "    with scanstats.stage('trace_probe'):\n"
            "        return x\n"
        )
        r = run_jaxlint(ok)
        assert r.returncode == 0, r.stdout

    def test_no_false_positives_on_accepted_idioms(self, tmp_path):
        """The idioms this tree actually uses must pass unsuppressed:
        static_argnames jit kernels over shapes, host numpy outside jit,
        dtype-pinned jnp constructors, the `self = object.__new__(cls)`
        classmethod constructor, lock-guarded mutation, and reasoned
        suppressions."""
        ok = hot_file(
            tmp_path,
            "import threading\n"
            "from functools import partial\n"
            "import jax\n"
            "import jax.numpy as jnp\n"
            "import numpy as np\n"
            "from horaedb_tpu.common.xprof import xjit\n"
            "\n"
            "@partial(xjit, static_argnames=('n',))\n"
            "def kernel(x, n):\n"
            "    # device-side jnp.asarray is not a sync; int dtype literals\n"
            "    # are exact; f-strings and prints live OUTSIDE the kernel\n"
            "    return jnp.asarray(x) + jnp.full((n,), 1, jnp.int32)\n"
            "\n"
            "def host_pack(cols):\n"
            "    # numpy->numpy on the host side of the kernel boundary\n"
            "    return np.asarray(cols), jnp.full((2,), 0.5, jnp.float32)\n"
            "\n"
            "def pinned():\n"
            "    return jnp.array([1.0], dtype=jnp.float32)\n"
            "\n"
            "class Registry:\n"
            "    def __init__(self):\n"
            "        raise RuntimeError('use Registry.open')\n"
            "    @classmethod\n"
            "    def open(cls):\n"
            "        self = object.__new__(cls)\n"
            "        self._lock = threading.Lock()\n"
            "        self._items = {}\n"  # unpublished instance: no race
            "        return self\n"
            "    def put(self, k, v):\n"
            "        with self._lock:\n"
            "            self._items[k] = v\n"
            "    def get(self, k):\n"
            "        return self._items.get(k)\n"  # reads are not flagged
            "    def bump(self):\n"
            "        # _hits is never mutated under the lock anywhere in\n"
            "        # the class, so the lock does not claim it: no J004\n"
            "        self._hits = getattr(self, '_hits', 0) + 1\n"
            "    def evict(self, k):\n"
            "        # jaxlint: disable=J004 single-threaded test helper\n"
            "        self._items.pop(k, None)\n"
        )
        r = run_jaxlint(ok)
        assert r.returncode == 0, r.stdout

    def test_suppression_without_reason_is_its_own_finding(self, tmp_path):
        bad = hot_file(
            tmp_path,
            "class C:\n"
            "    def __init__(self):\n"
            "        import threading\n"
            "        self._lock = threading.Lock()\n"
            "        self._n = 0\n"
            "    def reset(self):\n"
            "        with self._lock:\n"
            "            self._n = 0\n"
            "    def bump(self):\n"
            "        self._n += 1  # jaxlint: disable=J004\n"
        )
        r = run_jaxlint(bad)
        assert r.returncode != 0
        assert "J000" in r.stdout, r.stdout
        # the reason-less suppression does NOT silence the finding
        assert "J004" in r.stdout, r.stdout

    def test_suppression_covers_line_above(self, tmp_path):
        ok = hot_file(
            tmp_path,
            "class C:\n"
            "    def __init__(self):\n"
            "        import threading\n"
            "        self._lock = threading.Lock()\n"
            "        self._n = 0\n"
            "    def reset(self):\n"
            "        with self._lock:\n"
            "            self._n = 0\n"
            "    def bump(self):\n"
            "        # jaxlint: disable=J004 metrics counter, torn reads ok\n"
            "        self._n += 1\n"
        )
        r = run_jaxlint(ok)
        assert r.returncode == 0, r.stdout

    def test_missing_root_fails_loudly(self):
        r = run_jaxlint("no_such_dir_xyz")
        assert r.returncode != 0
        assert "does not exist" in r.stdout + r.stderr

    def test_j006_host_ufunc_inside_jit_fires(self, tmp_path):
        """np.add.at / np.<ufunc>.reduceat inside a jit body: concretizes
        tracers AND reinvents the registry's host lane — J006."""
        bad = hot_file(
            tmp_path,
            "import jax\n"
            "import numpy as np\n"
            "\n"
            "@jax.jit\n"
            "def kernel(grid, idx, v):\n"
            "    np.add.at(grid, idx, v)\n"            # J006
            "    s = np.add.reduceat(v, idx)\n"        # J006
            "    return grid, s\n"
        )
        r = run_jaxlint(bad)
        assert r.returncode != 0
        assert r.stdout.count("J006") == 2, r.stdout
        assert f"{bad}:6: J006" in r.stdout, r.stdout

    def test_j006_onehot_outside_registry_fires(self, tmp_path):
        """Large one-hot materializations (jax.nn.one_hot > 64 classes,
        == broadcasted_iota at rank 3+) in engine code outside
        ops/blockagg.py / ops/agg_registry.py are ad-hoc aggregation
        lanes — J006."""
        bad = hot_file(
            tmp_path,
            "import jax\n"
            "import jax.numpy as jnp\n"
            "\n"
            "def wide(x):\n"
            "    return jax.nn.one_hot(x, 4096)\n"     # J006: big one-hot
            "\n"
            "def iota_mat(rank):\n"
            "    oh = rank[..., None] == jax.lax.broadcasted_iota(\n"
            "        jnp.int32, (256, 512, 64), 2)\n"  # J006: rank-3 one-hot
            "    return oh\n"
        )
        r = run_jaxlint(bad)
        assert r.returncode != 0
        assert r.stdout.count("J006") == 2, r.stdout

    def test_j006_accepted_idioms_pass(self, tmp_path):
        """Host reduceat OUTSIDE jit (promql's window reductions, the
        registry's own lanes), small one-hots, rank-2 iota index masks,
        and reasoned suppressions must not fire."""
        ok = hot_file(
            tmp_path,
            "import jax\n"
            "import jax.numpy as jnp\n"
            "import numpy as np\n"
            "\n"
            "def window_reduce(val, idx):\n"
            "    # host side of the kernel boundary: the sanctioned place\n"
            "    return np.minimum.reduceat(val, idx)\n"
            "\n"
            "def small_embed(x):\n"
            "    return jax.nn.one_hot(x, 8)\n"
            "\n"
            "def index_mask(n, k):\n"
            "    return k[:, None] == jax.lax.broadcasted_iota(\n"
            "        jnp.int32, (4, n), 1)\n"
            "\n"
            "from horaedb_tpu.common.xprof import xjit\n"
            "\n"
            "@xjit(kernel='sup')\n"
            "def suppressed(grid, idx, v):\n"
            "    # jaxlint: disable=J006 measured: registry lane loses here\n"
            "    np.add.at(grid, idx, v)\n"
            "    return grid\n"
        )
        r = run_jaxlint(ok)
        assert r.returncode == 0, r.stdout

    def test_j007_naked_jit_in_hot_modules_fires(self, tmp_path):
        """Every naked-jit spelling in ops//parallel//promql/ is an error:
        decorator, partial-decorator, inline call, and the import-alias
        escape hatch — each silently bypasses xprof's compile telemetry."""
        bad = hot_file(
            tmp_path,
            "from functools import partial\n"
            "import jax\n"
            "from jax import jit\n"
            "\n"
            "@jax.jit\n"
            "def a(x):\n"
            "    return x\n"
            "\n"
            "@partial(jax.jit, static_argnames=('n',))\n"
            "def b(x, n):\n"
            "    return x + n\n"
            "\n"
            "c = jax.jit(lambda x: x)\n"
        )
        r = run_jaxlint(bad)
        assert r.returncode != 0
        assert r.stdout.count("J007") == 4, r.stdout  # import + 3 uses

    def test_j007_xjit_and_suppressions_pass(self, tmp_path):
        """The sanctioned spelling (xprof.xjit, any form) and reasoned
        suppressions pass; xjit-wrapped bodies STAY under the in-jit
        rules (a J001 host sync inside one still fires)."""
        ok = hot_file(
            tmp_path,
            "from functools import partial\n"
            "import jax\n"
            "from horaedb_tpu.common.xprof import xjit\n"
            "\n"
            "@xjit(kernel='a', static_argnames=('n',))\n"
            "def a(x, n):\n"
            "    return x + n\n"
            "\n"
            "@partial(xjit, static_argnames=('n',))\n"
            "def b(x, n):\n"
            "    return x + n\n"
            "\n"
            "c = xjit(lambda x: x, kernel='c')\n"
            "\n"
            "# jaxlint: disable=J007 A/B probe outside the query path\n"
            "d = jax.jit(lambda x: x)\n"
        )
        r = run_jaxlint(ok)
        assert r.returncode == 0, r.stdout
        bad = hot_file(
            tmp_path,
            "import numpy as np\n"
            "from horaedb_tpu.common.xprof import xjit\n"
            "\n"
            "@xjit(kernel='k')\n"
            "def k(x):\n"
            "    return np.asarray(x)\n"
        )
        r = run_jaxlint(bad)
        assert r.returncode != 0
        assert "J001" in r.stdout, r.stdout

    def test_j007_outside_hot_modules_not_flagged(self, tmp_path):
        """storage/, engine/, bench harnesses, and common/xprof.py itself
        keep plain jax.jit (the wrapper must be allowed to exist)."""
        d = tmp_path / "horaedb_tpu" / "common"
        d.mkdir(parents=True, exist_ok=True)
        f = d / "xprof.py"
        f.write_text(
            "import jax\n"
            "wrapped = jax.jit(lambda x: x)\n"
        )
        r = run_jaxlint(f)
        assert r.returncode == 0, r.stdout

    def test_j006_registry_modules_exempt_from_onehot(self, tmp_path):
        """ops/blockagg.py and ops/agg_registry.py ARE the registry: their
        one-hot materializations are the registered kernels themselves."""
        d = tmp_path / "horaedb_tpu" / "ops"
        d.mkdir(parents=True, exist_ok=True)
        f = d / "blockagg.py"
        f.write_text(
            "import jax\n"
            "import jax.numpy as jnp\n"
            "\n"
            "def compaction(rank):\n"
            "    return rank[..., None] == jax.lax.broadcasted_iota(\n"
            "        jnp.int32, (256, 512, 64), 2)\n"
        )
        r = run_jaxlint(f)
        assert r.returncode == 0, r.stdout


class TestJ008AppendHotPath:
    """J008: blocking object-store / parquet-encode calls reachable from
    the append hot path (ingest/, engine/) outside the flush executor
    module — flush work must stay behind engine/flush_executor.py."""

    def seeded(self, tmp_path, name="seeded.py", pkg="engine"):
        d = tmp_path / "horaedb_tpu" / pkg
        d.mkdir(parents=True, exist_ok=True)
        f = d / name
        f.write_text(
            "import pyarrow.parquet as pq\n"
            "\n"
            "async def append(store, table, payload):\n"
            "    pq.write_table(table, 'x.parquet')\n"        # J008 encode
            "    await store.put('k', payload)\n"             # J008 put
            "    await store.put_stream('k', payload)\n"      # J008 put
        )
        return f

    def test_fires_in_engine_and_ingest(self, tmp_path):
        for pkg in ("engine", "ingest"):
            r = run_jaxlint(self.seeded(tmp_path, pkg=pkg))
            # 3x J008, plus J018: the parquet encode also blocks the
            # event loop (async def, no offload) — both gates see it
            assert r.returncode == 4, r.stdout
            assert r.stdout.count("J008") == 3, r.stdout
            assert r.stdout.count("J018") == 1, r.stdout
            assert "parquet encode" in r.stdout
            assert ".put_stream()" in r.stdout

    def test_flush_executor_module_exempt(self, tmp_path):
        r = run_jaxlint(self.seeded(tmp_path, name="flush_executor.py"))
        # J008's module exemption holds; J018 still (correctly) flags
        # the un-offloaded parquet encode inside the coroutine
        assert "J008" not in r.stdout, r.stdout
        assert r.stdout.count("J018") == 1, r.stdout

    def test_outside_append_modules_not_flagged(self, tmp_path):
        """storage/ and objstore/ ARE the durability layer: their puts and
        parquet writers are the sanctioned implementation."""
        d = tmp_path / "horaedb_tpu" / "storage"
        d.mkdir(parents=True, exist_ok=True)
        f = d / "storage.py"
        f.write_text(
            "import pyarrow.parquet as pq\n"
            "\n"
            "async def write_sst(store, table, blob):\n"
            "    pq.write_table(table, 'x.parquet')\n"
            "    await store.put('k', blob)\n"
        )
        r = run_jaxlint(f)
        # storage/ is exempt from J008; the blocking parquet write in a
        # coroutine is still a J018 (the real tree offloads these)
        assert "J008" not in r.stdout, r.stdout
        assert r.stdout.count("J018") == 1, r.stdout

    def test_reasoned_suppression_accepted(self, tmp_path):
        d = tmp_path / "horaedb_tpu" / "engine"
        d.mkdir(parents=True, exist_ok=True)
        f = d / "meta.py"
        f.write_text(
            "async def write_descriptor(store, desc):\n"
            "    # jaxlint: disable=J008 control-plane descriptor write at open\n"
            "    await store.put('REGIONS', desc)\n"
        )
        r = run_jaxlint(f)
        assert r.returncode == 0, r.stdout


class TestJ009StoreBoundary:
    """J009: concrete ObjectStore constructors outside objstore/ must be
    immediate arguments of a ResilientStore(...) — the resilience
    boundary (retry/backoff, deadlines, breaker, horaedb_objstore_*)
    is decided at the construction site."""

    def seeded(self, tmp_path, body, pkg="engine", name="seeded.py"):
        d = tmp_path / "horaedb_tpu" / pkg
        d.mkdir(parents=True, exist_ok=True)
        f = d / name
        f.write_text(body)
        return f

    def test_naked_store_construction_fires(self, tmp_path):
        f = self.seeded(
            tmp_path,
            "from horaedb_tpu.objstore import LocalStore, MemStore\n"
            "from horaedb_tpu.objstore.s3 import S3LikeStore\n"
            "\n"
            "def build(cfg):\n"
            "    a = LocalStore(cfg.data_dir)\n"          # J009
            "    b = MemStore()\n"                        # J009
            "    return S3LikeStore(cfg)\n"               # J009
        )
        r = run_jaxlint(f)
        assert r.returncode == 3, r.stdout
        assert r.stdout.count("J009") == 3, r.stdout
        assert "ResilientStore" in r.stdout

    def test_wrapped_construction_passes(self, tmp_path):
        f = self.seeded(
            tmp_path,
            "from horaedb_tpu.objstore import LocalStore\n"
            "from horaedb_tpu.objstore.chaos import ChaosStore\n"
            "from horaedb_tpu.objstore.resilient import ResilientStore\n"
            "from horaedb_tpu.objstore.s3 import S3LikeStore\n"
            "\n"
            "def build(cfg, retry):\n"
            "    a = ResilientStore(LocalStore(cfg.data_dir), retry=retry)\n"
            "    b = ResilientStore(S3LikeStore(cfg), name='s3')\n"
            "    c = ChaosStore(LocalStore(cfg.data_dir))\n"  # harness wrap
            "    return a, b, c\n"
        )
        r = run_jaxlint(f)
        assert r.returncode == 0, r.stdout

    def test_objstore_modules_exempt(self, tmp_path):
        """objstore/ builds the stores — it IS the boundary."""
        f = self.seeded(
            tmp_path,
            "from horaedb_tpu.objstore import MemStore\n"
            "\n"
            "def fixture():\n"
            "    return MemStore()\n",
            pkg="objstore",
        )
        r = run_jaxlint(f)
        assert r.returncode == 0, r.stdout

    def test_reasoned_suppression_accepted(self, tmp_path):
        f = self.seeded(
            tmp_path,
            "from horaedb_tpu.objstore import MemStore\n"
            "\n"
            "def scratch():\n"
            "    # jaxlint: disable=J009 throwaway in-memory scratch space\n"
            "    return MemStore()\n",
        )
        r = run_jaxlint(f)
        assert r.returncode == 0, r.stdout


class TestJ010VisibilityBoundary:
    """J010: tombstone/retention row filtering is ONE shared helper
    (storage/visibility.apply_visibility). Consuming the visibility
    state's row-filtering fields anywhere else is an ad-hoc per-reader
    filter waiting to diverge between scan routes and compaction."""

    def seeded(self, tmp_path, body, rel="storage/seeded.py"):
        f = tmp_path / "horaedb_tpu" / rel
        f.parent.mkdir(parents=True, exist_ok=True)
        f.write_text(body)
        return f

    def test_adhoc_filter_fires(self, tmp_path):
        f = self.seeded(
            tmp_path,
            "def my_reader_filter(table, vis, ts):\n"
            "    keep = ts >= (vis.retention_floor_ms or 0)\n"   # J010
            "    for t in vis.tombstones:\n"                     # J010
            "        keep &= ts < t.time_range.start\n"
            "    return table.filter(keep)\n",
        )
        r = run_jaxlint(f)
        assert r.returncode == 2, r.stdout
        assert r.stdout.count("J010") == 2, r.stdout
        assert "apply_visibility" in r.stdout

    def test_shared_helper_module_exempt(self, tmp_path):
        f = self.seeded(
            tmp_path,
            "def apply_visibility(table, vis):\n"
            "    floor = vis.retention_floor_ms\n"
            "    return floor, list(vis.tombstones)\n",
            rel="storage/visibility.py",
        )
        r = run_jaxlint(f)
        assert r.returncode == 0, r.stdout

    def test_manifest_store_exempt(self, tmp_path):
        """The manifest package persists/loads/GCs the records — storing
        the state is not filtering rows with it."""
        f = self.seeded(
            tmp_path,
            "def gc(self, live):\n"
            "    return [t for t in self.tombstones if t.id in live]\n",
            rel="storage/manifest/seeded.py",
        )
        r = run_jaxlint(f)
        assert r.returncode == 0, r.stdout

    def test_construction_not_flagged(self, tmp_path):
        """Building a Visibility (keyword args) is producing the state,
        not consuming it — only attribute loads are flagged."""
        f = self.seeded(
            tmp_path,
            "from horaedb_tpu.storage.visibility import Visibility\n"
            "\n"
            "def build(tombs, floor):\n"
            "    return Visibility(table='t', time_column='ts',\n"
            "                      tombstones=tuple(tombs),\n"
            "                      retention_floor_ms=floor)\n",
        )
        r = run_jaxlint(f)
        assert r.returncode == 0, r.stdout

    def test_reasoned_suppression_accepted(self, tmp_path):
        f = self.seeded(
            tmp_path,
            "def debug_dump(vis):\n"
            "    # jaxlint: disable=J010 admin introspection dump, filters no rows\n"
            "    return [t.id for t in vis.tombstones]\n",
        )
        r = run_jaxlint(f)
        assert r.returncode == 0, r.stdout


class TestJ011AdmissionBoundary:
    """J011: server-layer query entry points must route through the
    admission scheduler (server/admission.py) — a handler calling
    `engine.query(...)` directly silently bypasses the concurrency cap,
    queue/stall backpressure, end-to-end deadline, tenant fairness, and
    the shed metrics."""

    def seeded(self, tmp_path, body, rel="server/handlers.py"):
        f = tmp_path / "horaedb_tpu" / rel
        f.parent.mkdir(parents=True, exist_ok=True)
        f.write_text(body)
        return f

    def test_direct_engine_query_fires(self, tmp_path):
        f = self.seeded(
            tmp_path,
            "async def handle_query(state, req):\n"
            "    out = await state.engine.query(req)\n"          # J011
            "    t = await state.engine.query_exemplars(req)\n"  # J011
            "    return out, t\n",
        )
        r = run_jaxlint(f)
        assert r.returncode == 2, r.stdout
        assert r.stdout.count("J011") == 2, r.stdout
        assert "admission" in r.stdout

    def test_bare_engine_name_fires(self, tmp_path):
        f = self.seeded(
            tmp_path,
            "async def lane(engine, req):\n"
            "    return await engine.query(req)\n",              # J011
        )
        r = run_jaxlint(f)
        assert r.returncode == 1, r.stdout
        assert "J011" in r.stdout

    def test_admission_module_exempt(self, tmp_path):
        """The funnel itself calls the engine — that is its job."""
        f = self.seeded(
            tmp_path,
            "async def run_query(controller, engine, req):\n"
            "    async with controller.slot():\n"
            "        return await engine.query(req)\n",
            rel="server/admission.py",
        )
        r = run_jaxlint(f)
        assert r.returncode == 0, r.stdout

    def test_outside_server_not_flagged(self, tmp_path):
        """The engine layer queries itself (regions fan out, PromQL
        evaluates) — the boundary is the SERVER layer only."""
        f = self.seeded(
            tmp_path,
            "async def fan_out(self, req):\n"
            "    return [await e.query(req) for e in self.engines]\n"
            "async def inner(engine, req):\n"
            "    return await engine.query(req)\n",
            rel="engine/seeded.py",
        )
        r = run_jaxlint(f)
        assert r.returncode == 0, r.stdout

    def test_non_engine_receiver_not_flagged(self, tmp_path):
        f = self.seeded(
            tmp_path,
            "async def lookup(state, req):\n"
            "    return await state.registry.query(req)\n",
        )
        r = run_jaxlint(f)
        assert r.returncode == 0, r.stdout

    def test_reasoned_suppression_accepted(self, tmp_path):
        f = self.seeded(
            tmp_path,
            "async def bench_lane(state, req):\n"
            "    # jaxlint: disable=J011 harness lane, admission measured separately\n"
            "    return await state.engine.query(req)\n",
        )
        r = run_jaxlint(f)
        assert r.returncode == 0, r.stdout


class TestJ012DecodeFunnel:
    """J012: encoded SST lanes decode in exactly one funnel
    (storage/encoding.py host codecs, ops/decode.py device kernels, the
    encoded reader path in storage/read.py). An ad-hoc np.cumsum over a
    delta buffer or a hand-rolled shift/mask unpack starts bit-exact and
    diverges the first time the sidecar format moves."""

    def seeded(self, tmp_path, body, rel="engine/seeded.py"):
        f = tmp_path / "horaedb_tpu" / rel
        f.parent.mkdir(parents=True, exist_ok=True)
        f.write_text(body)
        return f

    def test_funnel_primitive_call_fires(self, tmp_path):
        f = self.seeded(
            tmp_path,
            "def fast_read(lane):\n"
            "    a = decode_lane(lane)\n"                        # J012
            "    b = encoding.decode_blob(data)\n"               # J012
            "    return unpack_bits(buf, n, w)\n",               # J012
        )
        r = run_jaxlint(f)
        assert r.returncode == 3, r.stdout
        assert r.stdout.count("J012") == 3, r.stdout
        assert "funnel" in r.stdout

    def test_decode_shaped_op_on_encoded_buffer_fires(self, tmp_path):
        f = self.seeded(
            tmp_path,
            "def adhoc(enc_deltas, first):\n"
            "    ts = np.cumsum(enc_deltas) + first\n"           # J012
            "    ids = np.unpackbits(encoded_ids)\n"             # J012
            "    return ts, ids\n",
        )
        r = run_jaxlint(f)
        assert r.returncode == 2, r.stdout
        assert r.stdout.count("J012") == 2, r.stdout

    def test_accumulate_over_payload_fires(self, tmp_path):
        f = self.seeded(
            tmp_path,
            "def xor_decode(payload):\n"
            "    return np.bitwise_xor.accumulate(payload)\n",   # J012
        )
        r = run_jaxlint(f)
        assert r.returncode == 1, r.stdout
        assert "J012" in r.stdout

    def test_cumsum_on_plain_buffer_not_flagged(self, tmp_path):
        """Decode-shaped ops over NON-encoded data are normal numpy."""
        f = self.seeded(
            tmp_path,
            "def histogram(counts, lengths):\n"
            "    edges = np.cumsum(lengths)\n"
            "    return np.add.accumulate(counts), edges\n",
        )
        r = run_jaxlint(f)
        assert r.returncode == 0, r.stdout

    def test_funnel_modules_exempt(self, tmp_path):
        for rel in ("storage/encoding.py", "ops/decode.py",
                    "storage/read.py"):
            f = self.seeded(
                tmp_path,
                "def _decode(lane, payload):\n"
                "    d = np.cumsum(unpack_bits(payload, n, w))\n"
                "    return decode_lane(lane)\n",
                rel=rel,
            )
            r = run_jaxlint(f)
            assert r.returncode == 0, (rel, r.stdout)

    def test_reasoned_suppression_accepted(self, tmp_path):
        f = self.seeded(
            tmp_path,
            "def bench(lane):\n"
            "    # jaxlint: disable=J012 bench lane measuring the funnel's own decode rate\n"
            "    return decode_lane(lane, impl='host')\n",
        )
        r = run_jaxlint(f)
        assert r.returncode == 0, r.stdout


class TestJ013ServingFunnel:
    """J013: the serving tier's result cache / rollup artifacts are read
    at ONE planner choke point (engine/data.py) and mutated only through
    the invalidation funnel (storage write commit, compaction commit,
    tombstone path). A second lookup or an ad-hoc mutation is exactly
    how a cache serves stale data."""

    def seeded(self, tmp_path, body, rel="server/seeded.py"):
        f = tmp_path / "horaedb_tpu" / rel
        f.parent.mkdir(parents=True, exist_ok=True)
        f.write_text(body)
        return f

    def test_read_primitives_fire_outside_choke_point(self, tmp_path):
        f = self.seeded(
            tmp_path,
            "async def shortcut(cache, storage, key, segs, rng, b):\n"
            "    hit = cache.serving_get(key)\n"                  # J013
            "    plan = plan_rollups(storage, segs, rng, 0, b)\n"  # J013
            "    return await read_rollup(storage, plan)\n",       # J013
        )
        r = run_jaxlint(f)
        assert r.returncode == 3, r.stdout
        assert r.stdout.count("J013") == 3, r.stdout
        assert "choke point" in r.stdout

    def test_mutation_fires_outside_funnel(self, tmp_path):
        f = self.seeded(
            tmp_path,
            "def handler(cache, root):\n"
            "    cache.serving_invalidate(root, 'flush')\n"       # J013
            "    cache.serving_put(b'k', None, 0, root, {})\n",   # J013
            rel="engine/engine.py",
        )
        r = run_jaxlint(f)
        assert r.returncode == 2, r.stdout
        assert r.stdout.count("J013") == 2, r.stdout
        assert "invalidation funnel" in r.stdout

    def test_choke_point_and_funnel_modules_exempt(self, tmp_path):
        reads = (
            "async def q(self, cache, key, storage, segs, rng, b):\n"
            "    hit = cache.serving_get(key)\n"
            "    return plan_rollups(storage, segs, rng, 0, b)\n"
        )
        for rel in ("engine/data.py", "serving/cache.py",
                    "storage/rollup.py"):
            r = run_jaxlint(self.seeded(tmp_path, reads, rel=rel))
            assert r.returncode == 0, (rel, r.stdout)
        writes = (
            "def commit(cache, root):\n"
            "    cache.serving_invalidate(root, 'compact')\n"
        )
        for rel in ("storage/storage.py", "storage/compaction/executor.py",
                    "serving/cache.py"):
            r = run_jaxlint(self.seeded(tmp_path, writes, rel=rel))
            assert r.returncode == 0, (rel, r.stdout)
        # the reader is below the tier: it may call neither side
        for body in (reads, writes):
            r = run_jaxlint(self.seeded(tmp_path, body, rel="storage/read.py"))
            assert r.returncode != 0 and "J013" in r.stdout, r.stdout

    def test_unrelated_calls_not_flagged(self, tmp_path):
        f = self.seeded(
            tmp_path,
            "def other(cache, key):\n"
            "    cache.get(key)\n"
            "    cache.invalidate(key)\n"
            "    plan = make_plan(key)\n"
            "    return plan\n",
        )
        r = run_jaxlint(f)
        assert r.returncode == 0, r.stdout

    def test_reasoned_suppression_accepted(self, tmp_path):
        f = self.seeded(
            tmp_path,
            "def gate(cache):\n"
            "    # jaxlint: disable=J013 smoke gate asserting the funnel's own counters\n"
            "    return cache.serving_get(b'probe')\n",
        )
        r = run_jaxlint(f)
        assert r.returncode == 0, r.stdout


class TestJ014FunnelSubscribers:
    """J014: the invalidation funnel's consumer set is pinned — only the
    cache (serving/) and the rule evaluator (rules/) may subscribe to
    `serving_subscribe`/`serving_unsubscribe`. A third subscriber is a
    second standing-query engine growing outside the audited one."""

    def seeded(self, tmp_path, body, rel="engine/watcher.py"):
        f = tmp_path / "horaedb_tpu" / rel
        f.parent.mkdir(parents=True, exist_ok=True)
        f.write_text(body)
        return f

    def test_subscription_fires_outside_consumer_set(self, tmp_path):
        f = self.seeded(
            tmp_path,
            "def watch(cache, cb, token):\n"
            "    t = cache.serving_subscribe(cb)\n"       # J014
            "    cache.serving_unsubscribe(token)\n"       # J014
            "    return t\n",
        )
        r = run_jaxlint(f)
        assert r.returncode == 2, r.stdout
        assert r.stdout.count("J014") == 2, r.stdout
        assert "consumer set" in r.stdout

    def test_server_layer_also_in_scope(self, tmp_path):
        f = self.seeded(
            tmp_path,
            "def boot(cache, cb):\n"
            "    return cache.serving_subscribe(cb)\n",
            rel="server/main.py",
        )
        r = run_jaxlint(f)
        assert r.returncode == 1, r.stdout
        assert "J014" in r.stdout

    def test_consumer_modules_exempt(self, tmp_path):
        body = (
            "def init(cache, cb):\n"
            "    return cache.serving_subscribe(cb)\n"
        )
        for rel in ("serving/cache.py", "rules/engine.py",
                    "rules/sub/extra.py"):
            r = run_jaxlint(self.seeded(tmp_path, body, rel=rel))
            assert r.returncode == 0, (rel, r.stdout)

    def test_unrelated_subscribe_not_flagged(self, tmp_path):
        f = self.seeded(
            tmp_path,
            "def other(bus, cb):\n"
            "    bus.subscribe(cb)\n"
            "    bus.unsubscribe(cb)\n",
        )
        r = run_jaxlint(f)
        assert r.returncode == 0, r.stdout

    def test_reasoned_suppression_accepted(self, tmp_path):
        f = self.seeded(
            tmp_path,
            "def gate(cache, cb):\n"
            "    # jaxlint: disable=J014 harness asserting subscriber error isolation\n"
            "    return cache.serving_subscribe(cb)\n",
        )
        r = run_jaxlint(f)
        assert r.returncode == 0, r.stdout


class TestJ015MeteringFunnel:
    """J015: per-tenant accounting goes through telemetry/metering.py —
    a horaedb_tenant_* family, a `tenant` labelname, or a legacy name
    embedding a tenant label registered anywhere else forks the usage
    ledger."""

    def seeded(self, tmp_path, body, rel="server/billing.py"):
        f = tmp_path / "horaedb_tpu" / rel
        f.parent.mkdir(parents=True, exist_ok=True)
        f.write_text(body)
        return f

    def test_tenant_family_outside_funnel_fires(self, tmp_path):
        f = self.seeded(
            tmp_path,
            "def reg(m):\n"
            "    return m.counter('horaedb_tenant_writes_total')\n",
        )
        r = run_jaxlint(f)
        assert r.returncode == 1, r.stdout
        assert "J015" in r.stdout and "metering funnel" in r.stdout

    def test_tenant_labelname_fires(self, tmp_path):
        f = self.seeded(
            tmp_path,
            "def reg(m):\n"
            "    return m.gauge('horaedb_active', labelnames=('tenant',))\n",
        )
        r = run_jaxlint(f)
        assert r.returncode == 1, r.stdout
        assert "J015" in r.stdout

    def test_legacy_string_tenant_label_fires(self, tmp_path):
        f = self.seeded(
            tmp_path,
            "def bump(METRICS, t):\n"
            "    METRICS.inc('horaedb_rows_total{tenant=\"acme\"}')\n",
        )
        r = run_jaxlint(f)
        assert r.returncode == 1, r.stdout
        assert "J015" in r.stdout

    def test_funnel_module_exempt(self, tmp_path):
        body = (
            "def reg(m):\n"
            "    return m.counter('horaedb_tenant_writes_total',\n"
            "                     labelnames=('tenant',))\n"
        )
        f = self.seeded(tmp_path, body, rel="telemetry/metering.py")
        r = run_jaxlint(f)
        assert r.returncode == 0, r.stdout

    def test_untenanted_families_not_flagged(self, tmp_path):
        f = self.seeded(
            tmp_path,
            "def reg(m):\n"
            "    c = m.counter('horaedb_writes_total',\n"
            "                  labelnames=('table',))\n"
            "    m.inc('horaedb_rows_total{table=\"data\"}')\n"
            "    return c\n",
        )
        r = run_jaxlint(f)
        assert r.returncode == 0, r.stdout

    def test_reasoned_suppression_accepted(self, tmp_path):
        f = self.seeded(
            tmp_path,
            "def reg(m):\n"
            "    # jaxlint: disable=J015 bench harness measuring the funnel itself\n"
            "    return m.counter('horaedb_tenant_bench_total')\n",
        )
        r = run_jaxlint(f)
        assert r.returncode == 0, r.stdout


class TestJ016StackingFunnel:
    """J016: stacking/padding of query result lanes belongs to the query
    batcher (server/batching.py) and the sanctioned stacked kernels
    (ops/aggregate.py) — a stack/pad-shaped call over batch-lane-named
    buffers anywhere else is a second stacked-execution path."""

    def seeded(self, tmp_path, body, rel="engine/fastpath.py"):
        f = tmp_path / "horaedb_tpu" / rel
        f.parent.mkdir(parents=True, exist_ok=True)
        f.write_text(body)
        return f

    def test_stack_over_result_grids_fires(self, tmp_path):
        f = self.seeded(
            tmp_path,
            "import numpy as np\n"
            "def combine(result_grids):\n"
            "    return np.stack(result_grids)\n",
        )
        r = run_jaxlint(f)
        assert r.returncode == 1, r.stdout
        assert "J016" in r.stdout and "query batcher" in r.stdout

    def test_pad_over_batched_lane_fires(self, tmp_path):
        f = self.seeded(
            tmp_path,
            "import numpy as np\n"
            "def widen(batched_values, n):\n"
            "    return np.pad(batched_values, (0, n))\n",
        )
        r = run_jaxlint(f)
        assert r.returncode == 1, r.stdout
        assert "J016" in r.stdout

    def test_batcher_module_exempt(self, tmp_path):
        f = self.seeded(
            tmp_path,
            "import numpy as np\n"
            "def combine(result_grids):\n"
            "    return np.vstack(result_grids)\n",
            rel="server/batching.py",
        )
        r = run_jaxlint(f)
        assert r.returncode == 0, r.stdout

    def test_sanctioned_stacked_kernel_exempt(self, tmp_path):
        f = self.seeded(
            tmp_path,
            "import jax.numpy as jnp\n"
            "def stacked(ts_lanes):\n"
            "    return jnp.stack(ts_lanes)\n",
            rel="ops/aggregate.py",
        )
        r = run_jaxlint(f)
        assert r.returncode == 0, r.stdout

    def test_unrelated_stack_not_flagged(self, tmp_path):
        # stacking buffers that do not name a query lane (the promql
        # evaluator's per-series value matrices, blockagg's feature
        # planes) stays legal
        f = self.seeded(
            tmp_path,
            "import numpy as np\n"
            "def matrix(members):\n"
            "    return np.stack([m.values for m in members])\n",
        )
        r = run_jaxlint(f)
        assert r.returncode == 0, r.stdout

    def test_reasoned_suppression_accepted(self, tmp_path):
        f = self.seeded(
            tmp_path,
            "import numpy as np\n"
            "def bench(stacked_rows):\n"
            "    # jaxlint: disable=J016 harness measuring the stacked lane itself\n"
            "    return np.stack(stacked_rows)\n",
        )
        r = run_jaxlint(f)
        assert r.returncode == 0, r.stdout


class TestJ017ClusterFunnel:
    """J017: manifest snapshot views belong to the manifest package and
    the cluster replica funnel; assignment records mutate only through
    cluster/assignment.py's fenced CAS API."""

    def seeded(self, tmp_path, body, rel="engine/sync.py"):
        f = tmp_path / "horaedb_tpu" / rel
        f.parent.mkdir(parents=True, exist_ok=True)
        f.write_text(body)
        return f

    def test_manifest_view_outside_funnel_fires(self, tmp_path):
        f = self.seeded(
            tmp_path,
            "from horaedb_tpu.storage.manifest import read_snapshot\n"
            "async def peek(store, root):\n"
            "    return await read_snapshot(store, root + '/manifest/snapshot')\n",
        )
        r = run_jaxlint(f)
        assert r.returncode == 1, r.stdout
        assert "J017" in r.stdout and "replica funnel" in r.stdout

    def test_folded_view_outside_funnel_fires(self, tmp_path):
        f = self.seeded(
            tmp_path,
            "from horaedb_tpu.storage.manifest import read_folded_view\n"
            "async def tail(store, root):\n"
            "    return await read_folded_view(store, root)\n",
            rel="server/replicator.py",
        )
        r = run_jaxlint(f)
        assert r.returncode == 1, r.stdout
        assert "J017" in r.stdout

    def test_replica_module_exempt(self, tmp_path):
        f = self.seeded(
            tmp_path,
            "from horaedb_tpu.storage.manifest import read_folded_view\n"
            "async def tail(store, root):\n"
            "    return await read_folded_view(store, root)\n",
            rel="cluster/replica.py",
        )
        r = run_jaxlint(f)
        assert r.returncode == 0, r.stdout

    def test_manifest_package_exempt(self, tmp_path):
        f = self.seeded(
            tmp_path,
            "async def fold(store, path):\n"
            "    return await read_snapshot(store, path)\n",
            rel="storage/manifest/extra.py",
        )
        r = run_jaxlint(f)
        assert r.returncode == 0, r.stdout

    def test_assignment_mutation_outside_api_fires(self, tmp_path):
        f = self.seeded(
            tmp_path,
            "async def steal(store, me):\n"
            "    await store.put('metrics/cluster/assignment/7', me)\n",
            rel="server/sync.py",
        )
        r = run_jaxlint(f)
        assert r.returncode == 1, r.stdout
        assert "J017" in r.stdout and "fenced CAS" in r.stdout

    def test_assignment_path_helper_mutation_fires(self, tmp_path):
        f = self.seeded(
            tmp_path,
            "from horaedb_tpu.cluster.assignment import assignment_path\n"
            "async def clobber(store, root, data):\n"
            "    await store.put(assignment_path(root, 3), data)\n",
            rel="server/sync.py",
        )
        r = run_jaxlint(f)
        assert r.returncode == 1, r.stdout
        assert "J017" in r.stdout

    def test_assignment_module_exempt(self, tmp_path):
        f = self.seeded(
            tmp_path,
            "async def commit(store, root, ver, data):\n"
            "    await store.put_if_absent(\n"
            "        f'{root}/cluster/assignment/{ver}', data)\n",
            rel="cluster/assignment.py",
        )
        r = run_jaxlint(f)
        assert r.returncode == 0, r.stdout

    def test_unrelated_put_not_flagged(self, tmp_path):
        f = self.seeded(
            tmp_path,
            "async def save(store, path, data):\n"
            "    await store.put(path, data)\n",
            rel="server/sync.py",
        )
        r = run_jaxlint(f)
        assert r.returncode == 0, r.stdout

    def test_reasoned_suppression_accepted(self, tmp_path):
        f = self.seeded(
            tmp_path,
            "async def seed(store, data):\n"
            "    # jaxlint: disable=J017 harness seeding a corrupt record on purpose\n"
            "    await store.put('db/cluster/assignment/1', data)\n",
            rel="server/sync.py",
        )
        r = run_jaxlint(f)
        assert r.returncode == 0, r.stdout


class TestJ022TracedClientFunnel:
    """J022: outbound cluster-tier HTTP — session construction and verb
    calls on session-named receivers — belongs in the router's
    traced_request funnel (cluster/router.py is exempt: it IS it)."""

    def seeded(self, tmp_path, body, rel="cluster/sync.py"):
        f = tmp_path / "horaedb_tpu" / rel
        f.parent.mkdir(parents=True, exist_ok=True)
        f.write_text(body)
        return f

    def test_session_construction_fires(self, tmp_path):
        f = self.seeded(
            tmp_path,
            "import aiohttp\n"
            "def connect():\n"
            "    return aiohttp.ClientSession()\n",
        )
        r = run_jaxlint(f)
        assert r.returncode == 1, r.stdout
        assert "J022" in r.stdout and "traced" in r.stdout

    def test_verb_on_session_receiver_fires(self, tmp_path):
        f = self.seeded(
            tmp_path,
            "async def ping(session, url):\n"
            "    async with session.post(url, data=b'x') as resp:\n"
            "        return resp.status\n",
            rel="server/prober.py",
        )
        r = run_jaxlint(f)
        assert r.returncode == 1, r.stdout
        assert "J022" in r.stdout and "traced_request" in r.stdout

    def test_self_session_attribute_fires(self, tmp_path):
        f = self.seeded(
            tmp_path,
            "class C:\n"
            "    async def fetch(self, url):\n"
            "        async with self._session.get(url) as resp:\n"
            "            return await resp.read()\n",
        )
        r = run_jaxlint(f)
        assert r.returncode == 1, r.stdout
        assert "J022" in r.stdout

    def test_router_module_exempt(self, tmp_path):
        f = self.seeded(
            tmp_path,
            "import aiohttp\n"
            "class R:\n"
            "    async def _ensure(self):\n"
            "        self._session = aiohttp.ClientSession()\n"
            "        return self._session\n",
            rel="cluster/router.py",
        )
        r = run_jaxlint(f)
        assert r.returncode == 0, r.stdout

    def test_outside_scope_not_flagged(self, tmp_path):
        f = self.seeded(
            tmp_path,
            "import aiohttp\n"
            "def connect():\n"
            "    return aiohttp.ClientSession()\n",
            rel="objstore/s3like.py",
        )
        r = run_jaxlint(f)
        assert r.returncode == 0, r.stdout

    def test_unrelated_get_not_flagged(self, tmp_path):
        f = self.seeded(
            tmp_path,
            "def role(request, d):\n"
            "    return request.query.get('role') or d.get('role')\n",
            rel="server/views.py",
        )
        r = run_jaxlint(f)
        assert r.returncode == 0, r.stdout

    def test_reasoned_suppression_accepted(self, tmp_path):
        f = self.seeded(
            tmp_path,
            "import aiohttp\n"
            "def connect():\n"
            "    # jaxlint: disable=J022 bootstrap probe before the router exists\n"
            "    return aiohttp.ClientSession()\n",
        )
        r = run_jaxlint(f)
        assert r.returncode == 0, r.stdout


class TestJ023PartialGridFunnel:
    """J023: the partial-grid wire codec and coordinator merge belong in
    cluster/partial.py (exempt: it IS the funnel). Shadow definitions of
    the funnel names and ad-hoc in-place ufunc grid folds in
    cluster/server code fork the wire format / fold order behind the
    distributed bit-exactness guarantee."""

    def seeded(self, tmp_path, body, rel="cluster/scatter.py"):
        f = tmp_path / "horaedb_tpu" / rel
        f.parent.mkdir(parents=True, exist_ok=True)
        f.write_text(body)
        return f

    def test_shadow_merge_def_fires(self, tmp_path):
        f = self.seeded(
            tmp_path,
            "def merge_grids(parts):\n"
            "    return parts[0]\n",
        )
        r = run_jaxlint(f)
        assert r.returncode == 1, r.stdout
        assert "J023" in r.stdout and "partial.py" in r.stdout

    def test_shadow_async_encode_def_fires(self, tmp_path):
        f = self.seeded(
            tmp_path,
            "async def encode_partials(results):\n"
            "    return b''\n",
            rel="server/wire.py",
        )
        r = run_jaxlint(f)
        assert r.returncode == 1, r.stdout
        assert "J023" in r.stdout

    def test_inplace_ufunc_fold_fires(self, tmp_path):
        f = self.seeded(
            tmp_path,
            "import numpy as np\n"
            "def fold(grid, idx, part):\n"
            "    np.add.at(grid['sum'], idx, part['sum'])\n"
            "    np.minimum.at(grid['min'], idx, part['min'])\n",
            rel="server/agg.py",
        )
        r = run_jaxlint(f)
        assert r.returncode == 2, r.stdout
        assert "J023" in r.stdout and "merge_grids" in r.stdout

    def test_partial_module_exempt(self, tmp_path):
        f = self.seeded(
            tmp_path,
            "import numpy as np\n"
            "def merge_grids(parts):\n"
            "    acc = parts[0]\n"
            "    np.add.at(acc['sum'], 0, 1.0)\n"
            "    return acc\n",
            rel="cluster/partial.py",
        )
        r = run_jaxlint(f)
        assert r.returncode == 0, r.stdout

    def test_calling_funnel_not_flagged(self, tmp_path):
        f = self.seeded(
            tmp_path,
            "from horaedb_tpu.cluster.partial import merge_partials\n"
            "def gather(parts, order):\n"
            "    return merge_partials(parts, order=order)\n",
            rel="server/gather.py",
        )
        r = run_jaxlint(f)
        assert r.returncode == 0, r.stdout

    def test_outside_scope_not_flagged(self, tmp_path):
        f = self.seeded(
            tmp_path,
            "import numpy as np\n"
            "def fold(grid, idx, part):\n"
            "    np.add.at(grid['sum'], idx, part['sum'])\n",
            rel="storage/rollup_fold.py",
        )
        r = run_jaxlint(f)
        assert r.returncode == 0, r.stdout

    def test_reasoned_suppression_accepted(self, tmp_path):
        f = self.seeded(
            tmp_path,
            "import numpy as np\n"
            "def fold(grid, idx, part):\n"
            "    # jaxlint: disable=J023 single-fragment debug histogram, not a merge\n"
            "    np.add.at(grid['hist'], idx, part)\n",
        )
        r = run_jaxlint(f)
        assert r.returncode == 0, r.stdout
