import json
import os
import sys

# Tests run on 1 CPU device (the dry-run's 512-device flag is NOT set here
# on purpose — smoke tests and benches must see the real host).
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

# ---------------------------------------------------------------- lockgraph
# Opt-in dynamic lock-order checking (repro.analyze.lockgraph): with
# ANALYZE_LOCKGRAPH=1 a process-global tracer is installed BEFORE any
# repro module is imported (module-level locks like pipeline.GATE are
# created at import time), so the whole tier-1 run doubles as the dynamic
# corpus.  Any test whose execution adds an ABBA pair fails; the session
# summary (locks seen, order edges, cycles) is dumped to
# ANALYZE_LOCKGRAPH_JSON for the CI artifact.
_LG_TRACER = None
if os.environ.get("ANALYZE_LOCKGRAPH", "") not in ("", "0"):
    from repro.analyze import lockgraph as _lockgraph

    _LG_TRACER = _lockgraph.install()

from hypothesis import settings  # noqa: E402

settings.register_profile("ci", deadline=None, max_examples=25,
                          derandomize=True)
settings.load_profile("ci")

if _LG_TRACER is not None:
    import pytest

    @pytest.fixture(autouse=True)
    def _lockgraph_guard():
        """Fail the test that introduced a lock-order violation (eager
        ABBA detection happens at acquisition time, on any thread)."""
        before = len(_LG_TRACER.violations)
        yield
        fresh = _LG_TRACER.violations[before:]
        assert not fresh, (
            "lock-order violation(s) during this test: "
            + "; ".join(f"{v['pair'][0]} <-> {v['pair'][1]}" for v in fresh))

    def pytest_sessionfinish(session, exitstatus):
        summary = _LG_TRACER.summary()
        out = os.environ.get("ANALYZE_LOCKGRAPH_JSON")
        if out:
            with open(out, "w") as f:
                json.dump(summary, f, indent=2, sort_keys=True)
        tr = session.config.pluginmanager.get_plugin("terminalreporter")
        if tr is not None:
            tr.write_line(
                f"lockgraph: {len(summary['locks'])} locks, "
                f"{len(summary['edges'])} order edges, "
                f"{summary['acquisitions']} acquisitions, "
                f"{len(summary['cycles'])} cycles, "
                f"{len(summary['violations'])} violations")
        if summary["cycles"] or summary["violations"]:
            session.exitstatus = 3
