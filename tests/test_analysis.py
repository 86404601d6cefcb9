"""repro.analysis: per-rule firing/non-firing fixtures, suppression tiers.

Every rule is pinned from both sides: the incident pattern it exists to
catch must fire, and the repo's compliant idiom must stay silent — so a
rule can neither rot (stops firing) nor creep (starts flagging the
sanctioned pattern) without a test going red.
"""
from __future__ import annotations

import json
import subprocess
import sys
import textwrap
from pathlib import Path

import pytest

from repro.analysis import (
    AnalysisError,
    analyze_paths,
    load_baseline,
    write_baseline,
)
from repro.analysis.engine import ModuleInfo
from repro.analysis.rules import RULES, get_rule

REPO_ROOT = Path(__file__).resolve().parent.parent
SRC = REPO_ROOT / "src"


def check(rule_id: str, source: str, relpath: str = "core/mod.py"):
    """Run one rule over an inline snippet; [] if out of the rule's scope."""
    mi = ModuleInfo(Path(relpath), relpath, textwrap.dedent(source))
    rule = get_rule(rule_id)
    if not rule.applies(mi):
        return []
    return list(rule.check(mi))


def fires(rule_id: str, source: str, relpath: str = "core/mod.py") -> bool:
    return bool(check(rule_id, source, relpath))


# ---------------------------------------------------------------- registry


def test_registry_ids_unique_and_documented():
    ids = [r.id for r in RULES]
    assert len(ids) == len(set(ids))
    assert len(ids) >= 8
    for r in RULES:
        assert r.id.startswith("RPR") and len(r.id) == 6
        assert r.title
        # every docstring must carry the contract and a motivating incident
        assert r.__doc__ and "Incident" in r.__doc__, r.id

    with pytest.raises(KeyError):
        get_rule("RPR999")


# ------------------------------------------------------------------ RPR001


def test_rpr001_fires_on_eager_jax_import():
    assert fires("RPR001", "import jax\n")
    assert fires("RPR001", "import jax.numpy as jnp\n")
    assert fires("RPR001", "from jax.sharding import Mesh\n")
    # top-level try/except still executes at import time
    assert fires(
        "RPR001",
        """
        try:
            import jax
        except ImportError:
            jax = None
        """,
    )


def test_rpr001_silent_on_compliant():
    # lazy: inside a function
    assert not fires(
        "RPR001",
        """
        def kernel():
            import jax
            return jax
        """,
    )
    # TYPE_CHECKING imports never execute at runtime
    assert not fires(
        "RPR001",
        """
        from typing import TYPE_CHECKING
        if TYPE_CHECKING:
            import jax
        """,
    )
    # numpy is not a heavy import
    assert not fires("RPR001", "import numpy as np\n")
    # the kernels package may be jax-resident; the presets may not
    assert not fires("RPR001", "import jax\n", relpath="kernels/ops.py")
    assert not fires("RPR001", "import jax\n", relpath="kernels/ref.py")
    assert fires("RPR001", "import jax\n", relpath="configs/buffcut_paper.py")


# ------------------------------------------------------------------ RPR002


def test_rpr002_fires_on_unjoined_thread_and_unbounded_queue():
    out = check(
        "RPR002",
        """
        import threading

        def start():
            t = threading.Thread(target=work)
            t.start()
        """,
    )
    assert len(out) == 1

    assert fires("RPR002", "import queue\nq = queue.Queue()\n")
    # aliased from-import still resolves
    assert fires(
        "RPR002",
        "from queue import Queue as Q\n\ndef f():\n    return Q()\n",
    )


def test_rpr002_silent_on_compliant_lifecycles():
    # try/finally join in the creating function (core/pipeline.py idiom)
    assert not fires(
        "RPR002",
        """
        import threading

        def run():
            t = threading.Thread(target=work)
            t.start()
            try:
                consume()
            finally:
                t.join()
        """,
    )
    # registered closer: self._thread joined by close() (serve/session.py)
    assert not fires(
        "RPR002",
        """
        import threading

        class Worker:
            def __init__(self):
                self._thread = threading.Thread(target=self._run, daemon=True)
                self._thread.start()

            def close(self):
                self._thread.join(timeout=5.0)
        """,
    )
    # list-of-threads closer (distributed/shard_driver.py idiom)
    assert not fires(
        "RPR002",
        """
        import threading

        class Pool:
            def start(self):
                for i in range(4):
                    t = threading.Thread(target=work)
                    self._threads.append(t)
                    t.start()

            def _join_all(self):
                for t in self._threads:
                    t.join()
        """,
    )
    # tuple re-assignment onto self (core/prefetch.py idiom)
    assert not fires(
        "RPR002",
        """
        import threading
        import queue

        class Pump:
            def _start(self):
                q = queue.Queue(maxsize=4)
                t = threading.Thread(target=pump, daemon=True)
                self._q, self._thread = q, t
                t.start()

            def _shutdown(self):
                t, q = self._thread, self._q
                t.join(timeout=5.0)
        """,
    )
    assert not fires("RPR002", "import queue\nq = queue.Queue(maxsize=8)\n")


# ------------------------------------------------------------------ RPR003


def test_rpr003_fires_on_naive_reductions():
    # the literal PR 5 FennelParams bug
    assert fires("RPR003", "n_total = float(g.node_w.sum())\n")
    assert fires("RPR003", "x = float(np.sum(w[cross]))\n")
    assert fires("RPR003", "cap = l_max(float(g.node_w.sum()), k, eps)\n")
    # builtin sum feeding a total
    assert fires("RPR003", "total_w = sum(ws)\n")
    assert fires("RPR003", "p = P(n_total=sum(float(w) for w in ws))\n")
    # set iteration mutating label state
    assert fires(
        "RPR003",
        """
        def f(dirty, labels):
            for v in set(dirty):
                labels[v] = 0
        """,
    )


def test_rpr003_silent_on_canonical_reductions():
    assert not fires(
        "RPR003", "n_total = float(np.sum(node_w.astype(np.float64)))\n"
    )
    assert not fires(
        "RPR003", "m = float(g.edge_w.astype(np.float64).sum() / 2.0)\n"
    )
    # builtin sum not feeding totals/loads is fine (stats aggregation)
    assert not fires("RPR003", "n_bytes = sum(a.nbytes for a in arrays)\n")
    # sorted iteration is the sanctioned fix
    assert not fires(
        "RPR003",
        """
        def f(dirty, labels):
            for v in sorted(set(dirty)):
                labels[v] = 0
        """,
    )
    # read-only set iteration does not mutate partition state
    assert not fires(
        "RPR003",
        """
        def f(dirty, labels):
            acc = []
            for v in set(dirty):
                acc.append(labels[v])
        """,
    )
    # the rule is scoped to label-affecting modules
    assert not fires(
        "RPR003", "x = float(a.sum())\n", relpath="api/cli.py"
    )


# ------------------------------------------------------------------ RPR004


def test_rpr004_fires_on_global_randomness():
    assert fires("RPR004", "import numpy as np\nx = np.random.rand(3)\n")
    assert fires("RPR004", "import numpy as np\nnp.random.seed(0)\n")
    assert fires("RPR004", "import random\nrandom.shuffle(xs)\n")
    assert fires("RPR004", "from random import shuffle\n")


def test_rpr004_silent_on_seeded_generators():
    assert not fires(
        "RPR004",
        "import numpy as np\nrng = np.random.default_rng(17)\nx = rng.random(3)\n",
    )
    assert not fires(
        "RPR004",
        "import numpy as np\n\ndef f(rng: np.random.Generator):\n    return rng\n",
    )
    # tests/benchmarks own their process: exempt
    assert not fires(
        "RPR004",
        "import numpy as np\nx = np.random.rand(3)\n",
        relpath="tests/test_mod.py",
    )
    # a local variable named `random` is not the stdlib module
    assert not fires("RPR004", "random = make_thing()\ny = random.choice\n")


# ------------------------------------------------------------------ RPR005


def test_rpr005_fires_on_torn_write_patterns():
    rel = "core/checkpoint.py"
    # direct write to the final artifact
    assert fires(
        "RPR005",
        "def save(path, data):\n    with open(path, 'wb') as f:\n        f.write(data)\n",
        relpath=rel,
    )
    # replace without fsync (the torn-checkpoint bug the rule exists for)
    assert fires(
        "RPR005",
        """
        import os

        def save(tmp, final, data):
            with open(tmp, 'wb') as f:
                f.write(data)
            os.replace(tmp, final)
        """,
        relpath=rel,
    )
    assert fires(
        "RPR005", "import os\n\ndef f(a, b):\n    os.rename(a, b)\n", relpath=rel
    )


def test_rpr005_silent_on_durable_pattern():
    # the core/checkpoint.py idiom: tmp + flush + fsync + replace
    assert not fires(
        "RPR005",
        """
        import os

        def save(path, data):
            tmp = f"{path}.tmp"
            with open(tmp, 'wb') as f:
                f.write(data)
                f.flush()
                os.fsync(f.fileno())
            os.replace(tmp, path)
        """,
        relpath="core/checkpoint.py",
    )
    # rule is scoped: ordinary writes elsewhere are not checkpoint artifacts
    assert not fires(
        "RPR005",
        "def dump(path, s):\n    with open(path, 'w') as f:\n        f.write(s)\n",
        relpath="api/result.py",
    )


# ------------------------------------------------------------------ RPR006


def test_rpr006_fires_on_swallowed_and_unchained():
    assert fires("RPR006", "try:\n    work()\nexcept:\n    pass\n")
    assert fires("RPR006", "try:\n    work()\nexcept Exception:\n    pass\n")
    assert fires(
        "RPR006",
        """
        try:
            work()
        except ValueError:
            raise RuntimeError("wrapped")
        """,
    )


def test_rpr006_silent_on_disciplined_handling():
    # narrow type + pass is a legitimate best-effort cleanup
    assert not fires("RPR006", "try:\n    work()\nexcept OSError:\n    pass\n")
    # broad catch that records the error is fine
    assert not fires(
        "RPR006",
        "try:\n    work()\nexcept Exception as e:\n    log(e)\n",
    )
    # chained re-raises, both flavors
    assert not fires(
        "RPR006",
        """
        try:
            work()
        except ValueError as e:
            raise RuntimeError("wrapped") from e
        """,
    )
    assert not fires(
        "RPR006",
        """
        try:
            work()
        except ValueError:
            raise RuntimeError("severed") from None
        """,
    )
    # re-raising the caught exception itself needs no chain
    assert not fires(
        "RPR006",
        "try:\n    work()\nexcept ValueError as e:\n    raise\n",
    )


# ------------------------------------------------------------------ RPR007


def test_rpr007_fires_on_unmatched_stage():
    assert fires(
        "RPR007",
        """
        def apply(self, moved, old):
            self.cm.stage(moved, old)
            do_partition()
        """,
    )


def test_rpr007_silent_on_bracketed_stage_commit():
    # the MicroRestreamer idiom: stage and commit in the same function
    assert not fires(
        "RPR007",
        """
        def apply(self, moved, old, new):
            self.cm.stage(moved, old)
            labels = do_partition()
            self.cm.commit(moved, new)
            return labels
        """,
    )
    # different receivers are independent brackets
    assert fires(
        "RPR007",
        """
        def apply(self, moved, old, new):
            self.cm.stage(moved, old)
            other.commit(moved, new)
        """,
    )


# ------------------------------------------------------------------ RPR008


def test_rpr008_fires_on_raw_stream_open():
    rel = "graphs/newreader.py"
    assert fires(
        "RPR008",
        "def read(path):\n    with open(path, 'rb') as f:\n        return f.read()\n",
        relpath=rel,
    )
    # dynamic mode is a read until proven otherwise
    assert fires(
        "RPR008",
        "def opener(path, mode):\n    return open(path, mode)\n",
        relpath=rel,
    )


def test_rpr008_silent_on_routed_open():
    # the _retrying(lambda: open(...)) idiom is the compliant routing
    assert not fires(
        "RPR008",
        """
        def read(path, retry):
            with _retrying(lambda: open(path, 'rb'), retry) as f:
                return f.read()
        """,
        relpath="graphs/newreader.py",
    )
    # rule is scoped to graphs/: other packages open files normally
    assert not fires(
        "RPR008",
        "def read(path):\n    return open(path, 'rb').read()\n",
        relpath="core/config.py",
    )


# -------------------------------------------------------------- suppression


def test_noqa_suppresses_specific_rule_only(tmp_path):
    mod = tmp_path / "core" / "mod.py"
    mod.parent.mkdir()
    mod.write_text(
        "import jax  # repro: noqa RPR001 -- fixture\n"
        "import queue\n"
        "q = queue.Queue()\n"
    )
    report = analyze_paths([tmp_path])
    assert report.suppressed == 1
    assert [v.rule for v in report.new] == ["RPR002"]


def test_bare_noqa_suppresses_all_rules(tmp_path):
    mod = tmp_path / "core" / "mod.py"
    mod.parent.mkdir()
    mod.write_text("n_total = float(w.sum())  # repro: noqa\n")
    report = analyze_paths([tmp_path])
    assert report.new == [] and report.suppressed == 1


def test_plain_ruff_noqa_does_not_suppress(tmp_path):
    mod = tmp_path / "core" / "mod.py"
    mod.parent.mkdir()
    mod.write_text("import jax  # noqa\n")
    report = analyze_paths([tmp_path])
    assert [v.rule for v in report.new] == ["RPR001"]


# ----------------------------------------------------------------- baseline


def test_baseline_round_trip_add_then_remove(tmp_path):
    mod = tmp_path / "core" / "mod.py"
    mod.parent.mkdir()
    mod.write_text("import jax\nn_total = float(w.sum())\n")
    bl = tmp_path / "baseline.txt"

    # 1. violations are new with no baseline
    r1 = analyze_paths([tmp_path])
    assert {v.rule for v in r1.new} == {"RPR001", "RPR003"} and not r1.ok

    # 2. accept them; the run is now clean
    write_baseline(r1.new, bl)
    entries = load_baseline(bl)
    assert len(entries) == 2
    r2 = analyze_paths([tmp_path], baseline=entries)
    assert r2.ok and len(r2.baselined) == 2 and not r2.stale_baseline

    # 3. fingerprints are content-based: inserting a line above does not
    #    invalidate the baseline...
    mod.write_text("x = 1\nimport jax\nn_total = float(w.sum())\n")
    r3 = analyze_paths([tmp_path], baseline=entries)
    assert r3.ok and len(r3.baselined) == 2

    # 4. ...but fixing a violation makes its entry stale (remove half)
    mod.write_text("x = 1\nimport jax\n")
    r4 = analyze_paths([tmp_path], baseline=entries)
    assert r4.ok and len(r4.baselined) == 1 and len(r4.stale_baseline) == 1
    assert r4.stale_baseline[0]["rule"] == "RPR003"

    # 5. rewriting the baseline drops the stale entry, keeps justifications
    for fp in entries:
        entries[fp]["comment"] = f"justified {entries[fp]['rule']}"
    write_baseline(r4.baselined, bl, existing=entries)
    entries2 = load_baseline(bl)
    assert len(entries2) == 1
    (meta,) = entries2.values()
    assert meta["rule"] == "RPR001" and meta["comment"] == "justified RPR001"


def test_duplicate_line_occurrences_fingerprint_distinctly(tmp_path):
    mod = tmp_path / "core" / "mod.py"
    mod.parent.mkdir()
    mod.write_text("a = float(w.sum())\nb = 1\na = float(w.sum())\n")
    report = analyze_paths([tmp_path])
    fps = [v.fingerprint for v in report.new]
    assert len(fps) == 2 and len(set(fps)) == 2


def test_malformed_baseline_is_loud(tmp_path):
    bl = tmp_path / "baseline.txt"
    bl.write_text("# header ok\nnot-a-fingerprint RPR001 x.py:1\n")
    with pytest.raises(AnalysisError, match="malformed baseline"):
        load_baseline(bl)


def test_unknown_select_rule_is_loud(tmp_path):
    with pytest.raises(AnalysisError, match="unknown rule"):
        analyze_paths([tmp_path], select={"RPR999"})


def test_syntax_error_is_loud(tmp_path):
    bad = tmp_path / "bad.py"
    bad.write_text("def broken(:\n")
    with pytest.raises(AnalysisError, match="syntax error"):
        analyze_paths([tmp_path])


# -------------------------------------------------------- repo + CLI gates


def test_repo_is_clean_under_checked_in_baseline():
    """The acceptance gate, as a test: the tree passes its own linter."""
    entries = load_baseline(REPO_ROOT / "ANALYSIS_BASELINE.txt")
    report = analyze_paths([SRC / "repro"], baseline=entries)
    assert report.ok, "\n".join(
        f"{v.location}: {v.rule} {v.message}" for v in report.new
    )
    assert not report.stale_baseline


def test_cli_json_contract(tmp_path):
    mod = tmp_path / "core" / "mod.py"
    mod.parent.mkdir()
    mod.write_text("import jax\n")
    proc = subprocess.run(
        [sys.executable, "-m", "repro.analysis", "--format", "json",
         "--baseline", "none", str(tmp_path)],
        capture_output=True, text=True,
        env={"PYTHONPATH": str(SRC), "PATH": "/usr/bin:/bin"},
    )
    assert proc.returncode == 1
    data = json.loads(proc.stdout)
    assert data["version"] == 1 and not data["ok"]
    (v,) = data["violations"]
    assert v["rule"] == "RPR001" and v["path"] == "core/mod.py"
    assert v["line"] == 1 and v["fingerprint"]


def test_cli_exit_codes(tmp_path):
    clean = tmp_path / "core"
    clean.mkdir()
    (clean / "mod.py").write_text("x = 1\n")
    env = {"PYTHONPATH": str(SRC), "PATH": "/usr/bin:/bin"}
    ok = subprocess.run(
        [sys.executable, "-m", "repro.analysis", "--baseline", "none",
         str(tmp_path)],
        capture_output=True, text=True, env=env,
    )
    assert ok.returncode == 0, ok.stdout + ok.stderr
    usage = subprocess.run(
        [sys.executable, "-m", "repro.analysis", "--select", "RPR999",
         str(tmp_path)],
        capture_output=True, text=True, env=env,
    )
    assert usage.returncode == 2
    assert "unknown rule" in usage.stderr
