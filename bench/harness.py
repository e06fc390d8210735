"""What every cell shares: finding its files by name, building the
program's configuration from a configuration file, counting compilations,
watching snapshot flights and the states they save, and the shared-memory
hygiene of a run.

Everything a cell needs is found by name under `bench/`:
  configs/<config>.json     sizes as run, the source, the reference's name
  workloads/<cell>.json     configuration, traffic mix, chips, batch, limits
  traffic/<traffic>.json    the mix's parameters and the loop that reads it
  loops/<loop>.py           the window loop (`run(ctx) -> record`)
  metrics/<metric>.py       one reader per metric (`read(rec) -> value|None`)
  reference/<name>.py       the plain reference of a configuration
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
import sys
import threading

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
SRC = os.path.join(ROOT, "src")
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


# ------------------------------------------------------------ finding files
def load_json(*parts) -> dict:
    with open(os.path.join(BENCH, *parts)) as f:
        return json.load(f)


def load_module(kind: str, name: str):
    """`bench/<kind>/<name>.py` as a module (names may hold '-' and '.')."""
    path = os.path.join(BENCH, kind, name + ".py")
    here = os.path.dirname(path)
    if here not in sys.path:                  # siblings such as _common
        sys.path.insert(0, here)
    spec = importlib.util.spec_from_file_location(
        f"bench_{kind}_{name}".replace("-", "_").replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def benchmark_entries(workload: str, rehearsal: bool = False) -> dict:
    """This cell's entries of BENCHMARK.json (at the checkout's root).  A
    rehearsal may run a cell the file does not list yet; it reports no
    metrics, only its checks."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bm = json.load(f)
    cells = {w["name"]: w for w in bm["workloads"]}
    if workload not in cells:
        if rehearsal:
            return {"cell": None, "end_to_end": [], "per_layer": []}
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json")

    def mine(m):
        return "workloads" not in m or workload in m["workloads"]
    return {"cell": cells[workload],
            "end_to_end": [m for m in bm["end_to_end"] if mine(m)],
            "per_layer": [m for m in bm["per_layer"] if mine(m)]}


def reduced_conf(conf: dict, cfg) -> dict:
    """The configuration file's sizes replaced by the program's own
    smoke-test variant (CPU rehearsals only)."""
    out = dict(conf)
    out.update(num_hidden_layers=cfg.num_layers, hidden_size=cfg.d_model,
               num_attention_heads=cfg.num_heads,
               num_key_value_heads=cfg.num_kv_heads, head_dim=cfg.head_dim,
               ffn_dim=cfg.d_ff, vocab_size=cfg.vocab_size,
               param_dtype=cfg.param_dtype, compute_dtype=cfg.dtype)
    return out


def program_config(conf: dict, reduced: bool = False):
    """The program's ModelConfig for a configuration file, checked key by
    key against the file, so the two can never drift apart: the sizes, and
    the architectural switches the file's `program.architecture` states
    (family, experts, tied embeddings, ...), under the program's names."""
    from repro.configs import get_config
    prog = conf["program"]
    cfg = dataclasses.replace(get_config(prog["arch"]),
                              **prog.get("overrides", {}))
    if reduced:
        cfg = cfg.reduced()
        conf = reduced_conf(conf, cfg)
    want = {"num_layers": conf["num_hidden_layers"],
            "d_model": conf["hidden_size"],
            "num_heads": conf["num_attention_heads"],
            "num_kv_heads": conf.get("num_key_value_heads",
                                     conf["num_attention_heads"]),
            "head_dim": conf.get("head_dim", conf["hidden_size"]
                                 // conf["num_attention_heads"]),
            "d_ff": conf["ffn_dim"], "vocab_size": conf["vocab_size"],
            "rope_theta": conf["rope_theta"],
            "param_dtype": conf["param_dtype"],
            "dtype": conf["compute_dtype"], **prog["architecture"]}
    bad = {k: (getattr(cfg, k), v) for k, v in want.items()
           if getattr(cfg, k) != v}
    if bad:
        raise ValueError(f"program config {cfg.name} differs from "
                         f"{conf['name']}.json: {bad}")
    return cfg, conf


def adam_config(conf: dict):
    from repro.optim.adam import AdamConfig
    o = conf["optimizer"]
    return AdamConfig(lr=o["lr"], b1=o["b1"], b2=o["b2"], eps=o["eps"],
                      weight_decay=o["weight_decay"],
                      grad_clip=o["grad_clip"],
                      moments_dtype=conf["moment_dtype"])


# ------------------------------------------------------------- compilation
class CompileCounter:
    """Counts XLA compilations (persistent-cache loads included) from the
    event JAX records around every backend compile.  JAX keeps listeners
    for the life of the process: make one per process."""

    def __init__(self):
        from jax import monitoring
        self.n = 0
        self._lock = threading.Lock()
        monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration_secs, **kwargs):
        if event == COMPILE_EVENT:
            with self._lock:
                self.n += 1


# -------------------------------------------------------------- shm hygiene
SHM = "/dev/shm"


def shm_segments(run_id: str):
    prefix = f"reft-{run_id}-"
    try:
        names = os.listdir(SHM)
    except FileNotFoundError:
        return []
    return sorted(n for n in names if n.startswith(prefix))


def unlink_segments(run_id: str) -> int:
    """Remove every segment of this run; returns how many were left."""
    left = 0
    for name in shm_segments(run_id):
        try:
            os.unlink(os.path.join(SHM, name))
            left += 1
        except FileNotFoundError:
            pass
    return left


def shm_free() -> int:
    st = os.statvfs(SHM)
    return st.f_bavail * st.f_frsize


def shm_needed(state_bytes: int, sg: int, stage_bytes: int) -> int:
    """Bytes the SG's SMPs hold in /dev/shm: per member three buffers of
    its own region plus its parity block, and its staging ring."""
    n = sg
    bs = -(-state_bytes // (n * (n - 1)))
    return n * (3 * n * bs + stage_bytes + (1 << 20) * 3 + 4096)


# ------------------------------------------------------------ flight watch
class FlightWatch:
    """Every member's snapshot flights, read from the engines' newest
    flight handle after each step: when each began (the host time of the
    `after_step` that launched it), and on completion its own wall time
    and per-level seconds.  A member's newest flight is held until the
    member launches the next one, as the engine itself holds it."""

    def __init__(self):
        self.live = {}                    # node -> [flight, step, t0, seen]
        self.done = []                    # one dict per finished flight

    def poll(self, engines, now: float):
        for e in engines:
            cur = self.live.get(e.node)
            if cur is not None and not cur[3] and cur[0].done.is_set():
                self._record(e.node, cur)
            f = getattr(e, "_flight", None)
            if f is None or (cur is not None and f is cur[0]):
                continue
            if cur is not None and not cur[3]:       # superseded unseen
                self._record(e.node, cur)
            self.live[e.node] = [f, int(f.step), now, False]

    def _record(self, node, cur):
        f, step, t0, _ = cur
        cur[3] = True
        r, err = f.result, f.error
        rec = {"node": node, "step": step, "t_start": t0,
               "ok": r is not None and err is None}
        if r is not None:
            rec.update(wall=r.wall_seconds, l1=r.l1_seconds,
                       l1_stall=r.l1_stall_seconds, l2=r.l2_seconds,
                       l3=r.l3_seconds, bytes=r.bytes_sent)
        if err is not None:
            rec["error"] = f"{type(err).__name__}: {err}"
        self.done.append(rec)

    def finish(self):
        """After a drain: fold every flight not yet folded."""
        for node, cur in self.live.items():
            if not cur[3]:
                self._record(node, cur)

    def newest_steps(self):
        return {node: cur[1] for node, cur in self.live.items()}
