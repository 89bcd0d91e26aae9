"""evamlint (evam_tpu/analysis) — per-rule fixtures + whole-repo smoke.

Each pass gets a violating fixture (the finding must land with the
right pass id, ident and file:line) and a clean twin (no finding).
The smoke test then runs the real analyzer over the real repo and
requires exit 0 — the CI gate's exact contract — plus the satellite
policy: the allowlist carries no lock-discipline suppressions.
"""

from __future__ import annotations

import json
import textwrap
from pathlib import Path

import pytest

from evam_tpu.analysis import __main__ as cli
from evam_tpu.analysis import contracts, hotloop, imports_, knobs, locks
from evam_tpu.analysis.annotations import locked_by
from evam_tpu.analysis.core import (Allowlist, AllowlistError,
                                    iter_package_files, repo_root,
                                    run_passes)

REPO = repo_root()


def make_tree(root: Path, files: dict[str, str]) -> list:
    """Write a fixture repo under ``root`` and parse its package files."""
    for rel, text in files.items():
        p = root / rel
        p.parent.mkdir(parents=True, exist_ok=True)
        p.write_text(textwrap.dedent(text), encoding="utf-8")
    return iter_package_files(root)


# ------------------------------------------------------------------ locks

LOCKY = """
    import threading

    class Engine:
        SHARED_UNDER = {"stats": "_lock", "_pending": "_lock"}

        def __init__(self):
            self._lock = threading.Lock()
            self.stats = 0
            self._pending = []
"""


def test_locks_flags_unlocked_mutation(tmp_path):
    files = make_tree(tmp_path, {"evam_tpu/eng.py": LOCKY + """
        def bad(self):
            self.stats += 1
    """})
    found = locks.run(tmp_path, files)
    assert len(found) == 1
    f = found[0]
    assert (f.pass_id, f.ident) == ("locks", "unlocked:stats")
    assert f.file == "evam_tpu/eng.py"
    # the += is the last line of the fixture
    assert f.line == len((tmp_path / "evam_tpu/eng.py")
                         .read_text().splitlines())


def test_locks_receiver_method_is_mutation(tmp_path):
    files = make_tree(tmp_path, {"evam_tpu/eng.py": LOCKY + """
        def bad(self):
            self._pending.append(1)

        def read_ok(self):
            return list(self._pending)
    """})
    idents = {f.ident for f in locks.run(tmp_path, files)}
    assert idents == {"unlocked:_pending"}  # .append flagged, read not


def test_locks_clean_under_with(tmp_path):
    files = make_tree(tmp_path, {"evam_tpu/eng.py": LOCKY + """
        def good(self):
            with self._lock:
                self.stats += 1
                self._pending.append(1)
    """})
    assert locks.run(tmp_path, files) == []


def test_locks_locked_by_decorator(tmp_path):
    files = make_tree(tmp_path, {"evam_tpu/eng.py": """
        import threading
        from evam_tpu.analysis.annotations import locked_by

        class Engine:
            SHARED_UNDER = {"stats": "_lock"}

            def __init__(self):
                self._lock = threading.Lock()
                self.stats = 0

            @locked_by("_lock")
            def callers_hold(self):
                self.stats += 1
    """})
    assert locks.run(tmp_path, files) == []


def test_locks_locked_by_unknown_lock(tmp_path):
    files = make_tree(tmp_path, {"evam_tpu/eng.py": """
        from evam_tpu.analysis.annotations import locked_by

        class Engine:
            SHARED_UNDER = {"stats": "_lock"}

            @locked_by("_other")
            def callers_hold(self):
                self.stats += 1
    """})
    idents = {f.ident for f in locks.run(tmp_path, files)}
    assert any(i.startswith("locked-by-unknown:") for i in idents)


def test_locks_nested_def_escapes_lock(tmp_path):
    # a nested function runs later on an arbitrary thread: the lexical
    # `with` above it must NOT count as holding the lock
    files = make_tree(tmp_path, {"evam_tpu/eng.py": LOCKY + """
        def sneaky(self):
            with self._lock:
                def cb():
                    self.stats += 1
                return cb
    """})
    assert {f.ident for f in locks.run(tmp_path, files)} \
        == {"unlocked:stats"}


def test_locked_by_is_runtime_noop():
    @locked_by("_lock")
    def fn():
        return 41 + 1

    assert fn() == 42 and fn.__locked_by__ == "_lock"


# ---------------------------------------------------------------- hotloop

def test_hotloop_flags_env_read_in_loop(tmp_path):
    files = make_tree(tmp_path, {"evam_tpu/engine/batcher.py": """
        import os

        class BatchEngine:
            def _dispatch_loop(self):
                while True:
                    v = os.environ.get("EVAM_X")
    """})
    found = hotloop.run(tmp_path, files)
    assert len(found) == 1
    f = found[0]
    assert f.pass_id == "hotloop" and f.ident == "hotloop:os.environ"
    assert f.file == "evam_tpu/engine/batcher.py" and f.line == 7


def test_hotloop_read_before_loop_is_clean(tmp_path):
    files = make_tree(tmp_path, {"evam_tpu/engine/batcher.py": """
        import os

        class BatchEngine:
            def _dispatch_loop(self):
                v = os.environ.get("EVAM_X")
                while True:
                    use(v)
    """})
    assert hotloop.run(tmp_path, files) == []


def test_hotloop_propagates_through_calls(tmp_path):
    # loop -> self.method -> module fn -> time.sleep: still hot
    files = make_tree(tmp_path, {"evam_tpu/engine/batcher.py": """
        import time

        def helper():
            time.sleep(1)

        class BatchEngine:
            def _step(self):
                helper()

            def _completion_loop(self):
                while True:
                    self._step()
    """})
    found = hotloop.run(tmp_path, files)
    assert [(f.ident, f.line) for f in found] == [("hotloop:time.sleep", 5)]


def test_hotloop_non_entry_class_ignored(tmp_path):
    files = make_tree(tmp_path, {"evam_tpu/engine/batcher.py": """
        import os

        class NotAnEngine:
            def _dispatch_loop(self):
                while True:
                    os.environ.get("EVAM_X")
    """})
    assert hotloop.run(tmp_path, files) == []


# ------------------------------------------------------------------ knobs

KNOB_SETTINGS = """
    MAPPING = {"EVAM_FOO": ("foo", str)}
"""
KNOB_FAULTS = """
    ENV_KEYS = ("EVAM_FAULT_INJECT",)
"""


def knob_tree(tmp_path, surfaces_text: str, extra: dict | None = None):
    files = {
        "evam_tpu/config/settings.py": KNOB_SETTINGS,
        "evam_tpu/obs/faults.py": KNOB_FAULTS,
        "deploy/docker-compose.yml": surfaces_text,
        "deploy/helm/values.yaml": surfaces_text,
        "deploy/helm/templates/evam-deployment.yaml": surfaces_text,
        "README.md": surfaces_text,
    }
    files.update(extra or {})
    return make_tree(tmp_path, files)


def test_knobs_unplumbed_key(tmp_path):
    files = knob_tree(tmp_path, "EVAM_FAULT_INJECT only\n")
    found = knobs.run(tmp_path, files)
    # EVAM_FOO missing from each of the four surfaces
    assert sorted(f.ident for f in found) == [
        "unplumbed:EVAM_FOO:compose",
        "unplumbed:EVAM_FOO:helm-template",
        "unplumbed:EVAM_FOO:helm-values",
        "unplumbed:EVAM_FOO:readme",
    ]


def test_knobs_word_boundary(tmp_path):
    # EVAM_FOO_BAR does not satisfy EVAM_FOO
    files = knob_tree(tmp_path, "EVAM_FOO_BAR EVAM_FAULT_INJECT\n")
    found = knobs.run(tmp_path, files)
    assert {f.ident for f in found} == {
        f"unplumbed:EVAM_FOO:{s}"
        for s in ("compose", "helm-values", "helm-template", "readme")}


def test_knobs_env_read_outside_settings(tmp_path):
    files = knob_tree(
        tmp_path, "EVAM_FOO EVAM_FAULT_INJECT\n",
        extra={"evam_tpu/rogue.py": """
            import os
            MODE = os.environ.get("EVAM_MODE", "off")
            DYN = os.getenv("EVAM_" + "X")
        """})
    found = [f for f in knobs.run(tmp_path, files)
             if f.file == "evam_tpu/rogue.py"]
    assert {(f.ident, f.line) for f in found} == {
        ("env-read:EVAM_MODE", 3), ("env-read:dynamic", 4)}


def test_knobs_faults_must_export_env_keys(tmp_path):
    files = knob_tree(tmp_path, "EVAM_FOO EVAM_FAULT_INJECT\n")
    # overwrite faults.py without ENV_KEYS
    (tmp_path / "evam_tpu/obs/faults.py").write_text("KEYS = 1\n")
    files = iter_package_files(tmp_path)
    idents = {f.ident for f in knobs.run(tmp_path, files)}
    assert "faults-env-keys-missing" in idents


def test_knobs_clean(tmp_path):
    files = knob_tree(tmp_path, "EVAM_FOO and EVAM_FAULT_INJECT doc\n")
    assert knobs.run(tmp_path, files) == []


# -------------------------------------------------------------- contracts

CONTRACT_BASE = {
    "evam_tpu/obs/metrics.py": """
        METRIC_SPECS = {
            "evam_things": ("counter", ("engine",)),
        }
    """,
    "evam_tpu/engine/ringbuf.py": """
        STAGES = ("preprocess", "infer", "publish")
    """,
    "evam_tpu/sched/admission.py": """
        _SERVICE_STAGES = ("preprocess", "infer")
    """,
    "bench.py": """
        KEYS = ("preprocess", "infer", "streams_per_chip")
    """,
    "tests/test_server.py": """
        from evam_tpu.engine.ringbuf import STAGES
    """,
    "tests/test_bench_contract.py": """
        def test_line(data):
            assert {"streams_per_chip"} <= set(data)
    """,
}


def contract_tree(tmp_path, **overrides):
    files = dict(CONTRACT_BASE)
    files.update(overrides)
    return make_tree(tmp_path, files)


def test_contracts_clean(tmp_path):
    files = contract_tree(
        tmp_path,
        **{"evam_tpu/user.py": """
            from evam_tpu.obs.metrics import metrics
            metrics.inc("evam_things", labels={"engine": "a"})
        """})
    assert contracts.run(tmp_path, files) == []


def test_contracts_unregistered_metric(tmp_path):
    files = contract_tree(
        tmp_path,
        **{"evam_tpu/user.py": """
            from evam_tpu.obs.metrics import metrics
            metrics.inc("evam_things")
            metrics.inc("evam_ghost")
        """})
    found = contracts.run(tmp_path, files)
    assert [(f.ident, f.file, f.line) for f in found] == [
        ("metric-unregistered:evam_ghost", "evam_tpu/user.py", 4)]


def test_contracts_label_drift(tmp_path):
    files = contract_tree(
        tmp_path,
        **{"evam_tpu/user.py": """
            from evam_tpu.obs.metrics import metrics
            metrics.inc("evam_things", labels={"stream": "s"})
        """})
    idents = {f.ident for f in contracts.run(tmp_path, files)}
    assert idents == {"metric-labels:evam_things"}


def test_contracts_unused_spec(tmp_path):
    files = contract_tree(tmp_path)  # registered but never used
    idents = {f.ident for f in contracts.run(tmp_path, files)}
    assert idents == {"metric-unused:evam_things"}


def test_contracts_stage_order_drift(tmp_path):
    files = contract_tree(
        tmp_path,
        **{"evam_tpu/user.py": """
            from evam_tpu.obs.metrics import metrics
            metrics.inc("evam_things", labels={"engine": "a"})
        """,
           "evam_tpu/sched/admission.py": """
            _SERVICE_STAGES = ("infer", "preprocess")
        """})
    idents = {f.ident for f in contracts.run(tmp_path, files)}
    assert "stage-drift:preprocess" in idents


def test_contracts_bench_pin_without_producer(tmp_path):
    files = contract_tree(
        tmp_path,
        **{"evam_tpu/user.py": """
            from evam_tpu.obs.metrics import metrics
            metrics.inc("evam_things", labels={"engine": "a"})
        """,
           "tests/test_bench_contract.py": """
            def test_line(data):
                assert {"renamed_key"} <= set(data)
        """})
    found = [f for f in contracts.run(tmp_path, files)
             if f.ident.startswith("bench-key:")]
    assert [(f.ident, f.file) for f in found] == [
        ("bench-key:renamed_key", "tests/test_bench_contract.py")]


_CKPT_OK = """
    SCHEMA_VERSION = 1
    SCHEMA_V1_FIELDS = ("stream_id", "stages")

    class StreamCheckpoint:
        stream_id: str
        stages: dict
"""


def test_contracts_ckpt_schema_pinned_is_clean(tmp_path):
    files = contract_tree(
        tmp_path,
        **{"evam_tpu/user.py": """
            from evam_tpu.obs.metrics import metrics
            metrics.inc("evam_things", labels={"engine": "a"})
        """,
           "evam_tpu/state/checkpoint.py": _CKPT_OK})
    assert contracts.run(tmp_path, files) == []


def test_contracts_ckpt_field_change_without_bump_is_drift(tmp_path):
    files = contract_tree(
        tmp_path,
        **{"evam_tpu/user.py": """
            from evam_tpu.obs.metrics import metrics
            metrics.inc("evam_things", labels={"engine": "a"})
        """,
           "evam_tpu/state/checkpoint.py": """
            SCHEMA_VERSION = 1
            SCHEMA_V1_FIELDS = ("stream_id", "stages")

            class StreamCheckpoint:
                stream_id: str
                frame_seq: int
                stages: dict
        """})
    idents = {f.ident for f in contracts.run(tmp_path, files)}
    assert idents == {"ckpt-schema-drift"}


def test_contracts_ckpt_bump_without_new_pin_flagged(tmp_path):
    files = contract_tree(
        tmp_path,
        **{"evam_tpu/user.py": """
            from evam_tpu.obs.metrics import metrics
            metrics.inc("evam_things", labels={"engine": "a"})
        """,
           "evam_tpu/state/checkpoint.py": """
            SCHEMA_VERSION = 2
            SCHEMA_V1_FIELDS = ("stream_id", "stages")

            class StreamCheckpoint:
                stream_id: str
                stages: dict
        """})
    idents = {f.ident for f in contracts.run(tmp_path, files)}
    assert idents == {"ckpt-pin-missing"}


def test_contracts_repo_checkpoint_matches_live_dataclass(tmp_path):
    """The AST field walk must agree with dataclasses.fields() on the
    real module — the pin is only as strong as that equivalence."""
    import dataclasses

    from evam_tpu.state import checkpoint as ck_mod

    live = [f.name for f in dataclasses.fields(ck_mod.StreamCheckpoint)]
    assert tuple(live) == ck_mod.SCHEMA_V1_FIELDS
    assert ck_mod.SCHEMA_VERSION == 1


# ---------------------------------------------------------------- imports

def test_imports_cycle_detected(tmp_path):
    files = make_tree(tmp_path, {
        "evam_tpu/__init__.py": "",
        "evam_tpu/a.py": "from evam_tpu import b\n",
        "evam_tpu/b.py": "from evam_tpu import a\n",
    })
    found = imports_.run(tmp_path, files)
    assert len(found) == 1
    assert found[0].ident == "import-cycle:evam_tpu/a.py+evam_tpu/b.py"


def test_imports_deferred_import_breaks_cycle(tmp_path):
    files = make_tree(tmp_path, {
        "evam_tpu/__init__.py": "",
        "evam_tpu/a.py": "from evam_tpu import b\n",
        "evam_tpu/b.py": """
            def late():
                from evam_tpu import a
                return a
        """,
    })
    assert imports_.run(tmp_path, files) == []


def test_imports_submodule_import_not_a_package_edge(tmp_path):
    # `from evam_tpu import a` in __init__ + `from evam_tpu import b`
    # in a: binding a submodule name doesn't require the package
    # __init__ body, so this is NOT a cycle
    files = make_tree(tmp_path, {
        "evam_tpu/__init__.py": "from evam_tpu import a\n",
        "evam_tpu/a.py": "from evam_tpu import b\n",
        "evam_tpu/b.py": "",
    })
    assert imports_.run(tmp_path, files) == []


def test_layers_upward_deferred_import_found(tmp_path):
    # a deferred import breaks a cycle, not the layering: engine/
    # learning of server/ inside a function body is still a finding
    files = make_tree(tmp_path, {
        "evam_tpu/__init__.py": "",
        "evam_tpu/server/__init__.py": "",
        "evam_tpu/server/app.py": "",
        "evam_tpu/engine/__init__.py": "",
        "evam_tpu/engine/hub.py": """
            def late():
                from evam_tpu.server import app
                return app
        """,
    })
    found = imports_.run(tmp_path, files)
    assert [(f.ident, f.file, f.line) for f in found] == [
        ("layer-up:engine->server", "evam_tpu/engine/hub.py", 3)]


def test_layers_downward_deferred_import_passes(tmp_path):
    files = make_tree(tmp_path, {
        "evam_tpu/__init__.py": "",
        "evam_tpu/engine/__init__.py": "",
        "evam_tpu/engine/hub.py": "",
        "evam_tpu/server/__init__.py": "",
        "evam_tpu/server/app.py": """
            from evam_tpu.obs import log

            def late():
                from evam_tpu.engine import hub
                return hub
        """,
        "evam_tpu/obs/__init__.py": "",
        "evam_tpu/obs/log.py": "",
    })
    assert imports_.run(tmp_path, files) == []


@pytest.fixture(scope="module")
def repo_layer_findings():
    """The repo's raw upward imports (no allowlist applied), by ident."""
    return {f.ident: f for f in run_passes(REPO, ("imports",))
            if f.ident.startswith("layer-up:")}


@pytest.fixture(scope="module")
def layer_allowlist():
    return {e["ident"]: e for e in Allowlist.load(cli.ALLOWLIST).entries
            if e["ident"].startswith("layer-up:")}


def test_layers_name_every_package():
    """ONE total order over what is on disk: a new package takes its
    place in the table, or the check does not see it."""
    on_disk = {imports_._package(sf.rel) for sf in iter_package_files(REPO)}
    on_disk.discard("__init__")
    assert sorted(imports_.LAYERS) == sorted(on_disk)
    assert len(set(imports_.LAYERS)) == len(imports_.LAYERS)


@pytest.mark.parametrize("pkg", imports_.LAYERS)
def test_layer_imports_nothing_above_it(pkg, repo_layer_findings,
                                        layer_allowlist):
    """``pkg`` imports, top-level or deferred, nothing above it but
    what the allowlist names."""
    up = {i for i in repo_layer_findings if i.startswith(f"layer-up:{pkg}->")}
    assert up <= set(layer_allowlist), sorted(up - set(layer_allowlist))


@pytest.mark.parametrize("ident, file", [
    ("layer-up:engine->fleet", "evam_tpu/engine/hub.py"),
    ("layer-up:sched->stages", "evam_tpu/sched/admission.py"),
    ("layer-up:models->parallel", "evam_tpu/models/zoo/action.py"),
])
def test_layer_debt_is_still_owed(ident, file, repo_layer_findings,
                                  layer_allowlist):
    """Each allowlisted upward import is really there, in the file its
    justification names: once it is gone this fails, and the entry
    goes with it."""
    assert set(layer_allowlist) == {
        "layer-up:engine->fleet", "layer-up:sched->stages",
        "layer-up:models->parallel"}
    assert repo_layer_findings[ident].file == file
    assert file.removeprefix("evam_tpu/") in (
        layer_allowlist[ident]["justification"])


# -------------------------------------------------------------- allowlist

def test_allowlist_requires_justification(tmp_path):
    p = tmp_path / "allow.toml"
    p.write_text('[[allow]]\npass = "locks"\nident = "unlocked:x"\n')
    with pytest.raises(AllowlistError):
        Allowlist.load(p)


def test_allowlist_rejects_unknown_pass(tmp_path):
    p = tmp_path / "allow.toml"
    p.write_text('[[allow]]\npass = "nope"\nident = "x"\n'
                 'justification = "y"\n')
    with pytest.raises(AllowlistError):
        Allowlist.load(p)


def test_allowlist_stale_entry_reported(tmp_path):
    p = tmp_path / "allow.toml"
    p.write_text('[[allow]]\npass = "knobs"\nident = "env-read:EVAM_GONE"\n'
                 'justification = "long since fixed"\n')
    allow = Allowlist.load(p)
    assert allow.stale_entries() == allow.entries


# ------------------------------------------------------------- repo smoke

def test_repo_is_clean_end_to_end(tmp_path):
    """The CI gate's exact contract: full run, real allowlist, exit 0."""
    report = tmp_path / "report.json"
    assert cli.main(["--json", str(report)]) == 0
    data = json.loads(report.read_text())
    assert data["counts"]["findings"] == 0
    assert data["counts"]["stale_allowlist_entries"] == 0
    assert data["counts"]["allowlisted"] > 0  # documented suppressions


def test_lock_allowlist_is_empty():
    """Satellite policy: every lock-discipline finding gets fixed,
    never suppressed."""
    allow = Allowlist.load(cli.ALLOWLIST)
    assert [e for e in allow.entries if e["pass"] == "locks"] == []


def test_repo_locks_and_cycles_clean_without_allowlist():
    """The lock pass and the cycle check hold with NO allowlist at
    all — of these two passes the suppressions only cover the named
    upward imports."""
    assert [f for f in run_passes(REPO, ("locks", "imports"))
            if not f.ident.startswith("layer-up:")] == []


def test_knob_inventory_covers_fault_keys():
    files = iter_package_files(REPO)
    fkeys, missing = knobs.fault_keys(files)
    assert missing is None
    assert fkeys == {"EVAM_FAULT_INJECT", "EVAM_FAULT_SEED"}
    # and the settings surface is the big one (~37 keys)
    assert len(knobs.settings_keys(files)) >= 30


def test_cli_unknown_pass_is_internal_error():
    assert cli.main(["--passes", "bogus"]) == 2


def test_cli_pass_subset_skips_foreign_stale_entries():
    # knobs/hotloop allowlist entries must not read as stale when only
    # the locks+imports passes run
    assert cli.main(["--passes", "locks,imports"]) == 0
