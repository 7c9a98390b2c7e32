"""ctlint (cilium_tpu/analysis): each rule catches its bad corpus,
passes its good corpus, honors the disable allowlist — and the shipped
tree is clean (the `make lint` gate, asserted from the suite too so a
finding fails CI even if the lint lane is skipped)."""

import json
import os
import socket
import threading

import pytest

from cilium_tpu.analysis import run
from cilium_tpu.analysis.core import ProjectIndex
from cilium_tpu.analysis import abi as abi_rule
from cilium_tpu.analysis import configsurface as cfg_rule
from cilium_tpu.analysis import exceptions as exc_rule
from cilium_tpu.analysis import imports as imp_rule
from cilium_tpu.analysis import locks as lock_rule
from cilium_tpu.analysis import purity as purity_rule
from cilium_tpu.analysis import recompile as rec_rule
from cilium_tpu.analysis import registry as reg_rule
from cilium_tpu.analysis import shapes as shape_rule

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _check(sources, checker, **kw):
    """Run one rule over an in-memory corpus, applying the same
    disable filtering core.run does."""
    index, errors = ProjectIndex.from_sources(sources)
    assert not errors, errors
    out = []
    for f in checker(index, **kw):
        sf = index.by_path.get(f.path)
        if sf is not None and sf.disabled(f.line, f.rule):
            continue
        out.append(f)
    return out


# -- jit-purity -------------------------------------------------------------

PURITY_BAD = """\
import time

import jax
import jax.numpy as jnp


def helper(x):
    return x + time.time()


@jax.jit
def kernel(x):
    if jnp.any(x > 0):
        return helper(x)
    return x
"""

PURITY_GOOD = """\
import jax
import jax.numpy as jnp


def helper(x):
    return jnp.sum(x)


@jax.jit
def kernel(x):
    return jnp.where(x > 0, helper(x), x)
"""


def test_purity_bad_corpus():
    findings = _check({"pkg/kern.py": PURITY_BAD}, purity_rule.check)
    msgs = "\n".join(f.message for f in findings)
    assert any(f.rule == "jit-purity" for f in findings)
    assert "time.time" in msgs           # impure call via helper
    assert "traced value" in msgs        # if jnp.any(...) branch


def test_purity_good_corpus():
    assert _check({"pkg/kern.py": PURITY_GOOD}, purity_rule.check) == []


def test_purity_jit_call_form_and_lock():
    src = (
        "import threading\n"
        "import jax\n"
        "LOCK = threading.Lock()\n"
        "def step(x):\n"
        "    with LOCK:\n"
        "        return x\n"
        "fn = jax.jit(step)\n"
    )
    findings = _check({"pkg/m.py": src}, purity_rule.check)
    assert any("lock acquisition" in f.message for f in findings)


# -- lock-order -------------------------------------------------------------

LOCKS_CYCLE = """\
import threading


class A:
    def __init__(self):
        self._lock = threading.Lock()

    def do(self):
        with self._lock:
            B_SINGLETON.poke()


class B:
    def __init__(self):
        self._lock = threading.Lock()

    def poke(self):
        with self._lock:
            pass

    def back(self):
        with self._lock:
            A_SINGLETON.do()


A_SINGLETON = A()
B_SINGLETON = B()
"""

LOCKS_SELF_DEADLOCK = """\
import threading


class C:
    def __init__(self):
        self._lock = threading.Lock()
        self._cond = threading.Condition(self._lock)

    def outer(self):
        with self._cond:
            self.inner()

    def inner(self):
        with self._lock:
            pass
"""

LOCKS_GOOD = """\
import threading


class C:
    def __init__(self):
        self._lock = threading.RLock()

    def outer(self):
        with self._lock:
            self.inner()

    def inner(self):
        with self._lock:
            pass
"""


def test_lock_cycle_detected():
    findings = _check({"pkg/m.py": LOCKS_CYCLE}, lock_rule.check)
    assert any("lock-order cycle" in f.message for f in findings)


def test_lock_condition_alias_self_deadlock():
    # with self._cond holds the WRAPPED self._lock: calling a method
    # that re-takes self._lock is a one-thread deadlock
    findings = _check({"pkg/m.py": LOCKS_SELF_DEADLOCK},
                      lock_rule.check)
    assert any("self-deadlock" in f.message for f in findings)


def test_lock_rlock_reentry_allowed():
    assert _check({"pkg/m.py": LOCKS_GOOD}, lock_rule.check) == []


# -- metric-registry --------------------------------------------------------

METRICS_DECL = """\
METRICS.describe("cilium_tpu_good_total", "declared counter")
METRICS.describe("cilium_tpu_depth", "declared gauge")
"""

METRICS_BAD = """\
METRICS.inc("cilium_tpu_good_total")
METRICS.inc("cilium_tpu_typo_total")            # undeclared
METRICS.inc("cilium_tpu_requests")              # counter w/o _total
METRICS.set_gauge("cilium_tpu_good_total", 1)   # kind conflict
METRICS.observe("cilium tpu bad name", 1.0)     # illegal name
v = METRICS.get("cilium_tpu_never_written_total")
"""

METRICS_GOOD = """\
METRICS.inc("cilium_tpu_good_total")
METRICS.set_gauge("cilium_tpu_depth", 3)
v = METRICS.get("cilium_tpu_good_total")
"""


def test_metric_registry_bad_corpus():
    findings = _check(
        {"pkg/decl.py": METRICS_DECL, "pkg/use.py": METRICS_BAD},
        reg_rule.check_metrics, decl_module="pkg.decl")
    msgs = "\n".join(f.message for f in findings)
    assert "cilium_tpu_typo_total` written here but never declared" \
        in msgs
    assert "must end in `_total`" in msgs
    assert "conflicting instrument kinds" in msgs
    assert "not a legal Prometheus metric name" in msgs
    assert "nothing in the package writes it" in msgs


def test_metric_registry_good_corpus():
    assert _check(
        {"pkg/decl.py": METRICS_DECL, "pkg/use.py": METRICS_GOOD},
        reg_rule.check_metrics, decl_module="pkg.decl") == []


# -- fault-registry ---------------------------------------------------------

FAULTS_BAD = """\
from pkg import faults

GOOD_POINT = faults.register_point("seam.good", "covered")
DEAD_POINT = faults.register_point("seam.dead", "no seam")


def covered():
    faults.maybe_fail(GOOD_POINT)


def drifted():
    faults.maybe_fail("seam.ghost")
"""


def test_fault_registry_drift():
    findings = _check(
        {"pkg/faults.py": "def register_point(n, d=''):\n    return n\n"
                          "def maybe_fail(p):\n    pass\n",
         "pkg/seams.py": FAULTS_BAD},
        reg_rule.check_faults, faults_module="pkg.faults")
    msgs = "\n".join(f.message for f in findings)
    assert "seam.ghost" in msgs and "unregistered" in msgs
    assert "seam.dead" in msgs and "dead injection point" in msgs
    assert "seam.good" not in msgs


# -- frame-kind -------------------------------------------------------------

FRAMES_BAD = """\
KIND_A = 0
KIND_B = 1


class Server:
    def _work(self, kind):
        if kind == KIND_A:
            return "a"
        if kind == KIND_B:
            return "b"


class Client:
    def _recv(self, kind):
        if kind == KIND_A:
            return "a"
        return "??"  # KIND_B falls through — the gap
"""


def test_frame_kind_gap():
    findings = _check(
        {"pkg/proto.py": FRAMES_BAD}, reg_rule.check_frames,
        defs_module="pkg.proto",
        sites=(("pkg.proto", "Server", ("_work",)),
               ("pkg.proto", "Client", ("_recv",))))
    assert len(findings) == 1
    assert "KIND_B" in findings[0].message
    assert "Client" in findings[0].message


def test_frame_kind_duplicate_value():
    src = "KIND_A = 0\nKIND_B = 0\n"
    findings = _check({"pkg/proto.py": src}, reg_rule.check_frames,
                      defs_module="pkg.proto", sites=())
    assert any("reuses wire value" in f.message for f in findings)


# -- swallowed-exception / unused-import ------------------------------------

def test_swallowed_exception():
    src = (
        "def f():\n"
        "    try:\n"
        "        g()\n"
        "    except Exception:\n"
        "        pass\n"
        "def h():\n"
        "    try:\n"
        "        g()\n"
        "    except:\n"
        "        return 1\n"
        "def ok():\n"
        "    try:\n"
        "        g()\n"
        "    except ValueError:\n"
        "        pass\n"
    )
    findings = _check({"pkg/m.py": src}, exc_rule.check)
    assert len(findings) == 2
    assert {f.line for f in findings} == {4, 9}


def test_unused_import():
    src = "import os\nimport sys\n\nprint(sys.argv)\n"
    findings = _check({"pkg/m.py": src}, imp_rule.check)
    assert [f.line for f in findings] == [1]
    # __init__ re-export surfaces are exempt
    assert _check({"pkg/__init__.py": "import os\n"},
                  imp_rule.check) == []


# -- shape-dtype (dataflow core) --------------------------------------------

SHAPES_BAD = """\
import jax
import jax.numpy as jnp


@jax.jit
def kernel(
    table: jax.Array,   # [128, 64] int32
    probe: jax.Array,   # [100] int32
    data: jax.Array,    # [B, L] uint8
    lengths: jax.Array, # [B] int32
):
    bad = table[:, 0] + probe          # 128 vs 100 broadcast
    acc = jnp.sum(lengths)             # int32 acc over unknown B
    wrapped = data + 1000              # uint8 wrap
    idx = jnp.argmax(data, axis=1)     # [B]
    picked = jnp.take_along_axis(data, idx, axis=1)  # rank 2 vs 1
    resh = table.reshape(32, 64)       # 8192 -> 2048 elements
    mm = table @ table                 # 64 vs 128 contraction
    return bad, acc, wrapped, picked, resh, mm
"""

SHAPES_GOOD = """\
import jax
import jax.numpy as jnp


def fold(words, lengths):
    ok = words & jnp.uint32(1)
    return jnp.sum(ok, axis=1, dtype=jnp.uint32)


@jax.jit
def kernel(
    trans: jax.Array,     # [S, K] int32
    byteclass: jax.Array, # [256] int32
    data: jax.Array,      # [B, L] uint8
    lengths: jax.Array,   # [B] int32
):
    cls = byteclass[data.astype(jnp.int32)]        # [B, L]
    valid = (jnp.arange(data.shape[1])[None, :]
             < lengths[:, None])
    rows = jnp.where(valid, cls, 0)
    return fold(rows.astype(jnp.uint32), lengths)
"""


def test_shape_dtype_bad_corpus():
    findings = _check({"pkg/kern.py": SHAPES_BAD}, shape_rule.check)
    msgs = "\n".join(f.message for f in findings)
    assert "shape mismatch in `Add`" in msgs          # broadcast
    assert "int32-overflow-prone accumulation" in msgs
    assert "weak-type wrap: int literal 1000" in msgs
    assert "`take_along_axis` requires equal ranks" in msgs
    assert "reshape element-count mismatch" in msgs
    assert "matmul contraction mismatch" in msgs
    assert all(f.rule == "shape-dtype" for f in findings)


def test_shape_dtype_good_corpus():
    assert _check({"pkg/kern.py": SHAPES_GOOD}, shape_rule.check) == []


def test_shape_dtype_symbolic_dims_do_not_conflict():
    # distinct symbols are unknown-compatible (miss, don't invent):
    # [B] + [N] must NOT be a finding
    src = (
        "import jax\n"
        "@jax.jit\n"
        "def k(\n"
        "    a,  # [B] int32\n"
        "    b,  # [N] int32\n"
        "):\n"
        "    return a + b\n"
    )
    assert _check({"pkg/m.py": src}, shape_rule.check) == []


def test_shape_dtype_interprocedural():
    """The violation sits in a helper; only the jitted entry reaches
    it — the callgraph walk must carry the shapes across the call."""
    src = (
        "import jax\n"
        "import jax.numpy as jnp\n"
        "def helper(x, y):\n"
        "    return x + y\n"
        "@jax.jit\n"
        "def k(\n"
        "    a,  # [8] int32\n"
        "    b,  # [9] int32\n"
        "):\n"
        "    return helper(a, b)\n"
    )
    findings = _check({"pkg/m.py": src}, shape_rule.check)
    assert len(findings) == 1
    assert findings[0].line == 4  # inside helper, where the op is


def test_shape_entries_nonvacuous():
    """The dataflow walk must SEE the real tree's jitted surface —
    a refactor that breaks entry discovery goes loudly, not quietly."""
    index, _ = ProjectIndex.from_tree(REPO_ROOT, ("cilium_tpu",))
    assert shape_rule.entry_count(index) >= 8


# -- recompile-hazard -------------------------------------------------------

REWRAP_BAD = """\
import jax


def hot(x):
    fn = jax.jit(lambda v: v + 1)
    return fn(x)
"""

REWRAP_GOOD = """\
import functools

import jax


def step(x):
    return x


STEP = jax.jit(step)           # module-level: one wrapper, ever


class Engine:
    def __init__(self):
        self._cache = {}
        self._step = jax.jit(step)      # memoized onto self

    def blob(self, layout):
        fn = self._cache.get(layout)
        if fn is None:
            fn = jax.jit(step)          # memoized via a self dict
            self._cache[layout] = fn
        return fn


@functools.lru_cache(maxsize=None)
def factory(mesh):
    return jax.jit(step)                # cached factory
"""

DYNAMIC_BAD = """\
import jax
import jax.numpy as jnp


@jax.jit
def shaped(cfg, data):
    B, L = data.shape
    pad = (-L) % 8
    if pad:
        data = jnp.pad(data, ((0, 0), (0, pad)))
    out = jnp.zeros((cfg.engine.batch_size, 4))
    return data, out
"""


def test_recompile_rewrap_bad():
    findings = _check({"pkg/m.py": REWRAP_BAD}, rec_rule.check)
    assert len(findings) == 1
    assert "`jax.jit` built per call inside `hot`" in findings[0].message


def test_recompile_rewrap_good_patterns_exempt():
    assert _check({"pkg/m.py": REWRAP_GOOD}, rec_rule.check) == []


def test_recompile_dynamic_faces():
    findings = _check({"pkg/m.py": DYNAMIC_BAD}, rec_rule.check)
    msgs = "\n".join(f.message for f in findings)
    assert "shape-dependent Python branch on `pad`" in msgs
    assert "config-derived scalar `cfg.engine.batch_size`" in msgs


def test_recompile_shape_guard_raise_is_exempt():
    # `if S > cap: raise` is trace-time validation, not churn
    src = (
        "import jax\n"
        "@jax.jit\n"
        "def k(\n"
        "    t,  # [S, K] int32\n"
        "):\n"
        "    if t.shape[0] > 128:\n"
        "        raise ValueError('too big')\n"
        "    return t\n"
    )
    assert _check({"pkg/m.py": src}, rec_rule.check) == []


# -- abi-surface ------------------------------------------------------------

ABI_CPP = """\
extern \"C\" {

int cshim_ping(uint32_t id, const uint8_t* buf, size_t len) { return 0; }

long cshim_pull(void) { return 0; }

uint32_t cshim_rev() { return 0; }

void cshim_quiet() {}

}  // extern \"C\"
"""

ABI_BAD_PY = """\
import ctypes

lib = ctypes.CDLL("x.so")
lib.cshim_ping.argtypes = [ctypes.c_uint32, ctypes.c_void_p]
lib.cshim_ping(1, b"x", 3, 9)
lib.cshim_pull()
lib.cshim_gone.restype = ctypes.c_int
lib.cshim_rev.restype = ctypes.c_uint32
lib.cshim_rev()
"""

ABI_GOOD_PY = """\
import ctypes

lib = ctypes.CDLL("x.so")
lib.cshim_ping.argtypes = [ctypes.c_uint32, ctypes.c_void_p,
                           ctypes.c_size_t]
lib.cshim_ping(1, b"x", 3)
lib.cshim_pull.restype = ctypes.c_long
lib.cshim_pull()
lib.cshim_rev.restype = ctypes.c_uint32
lib.cshim_rev()
lib.cshim_quiet.restype = None
lib.cshim_quiet()
"""


def _abi_check(py_sources, cpp):
    index, errors = ProjectIndex.from_sources(py_sources)
    assert not errors
    return abi_rule.check_abi(index, cpp_sources={"shim/x.cpp": cpp})


def test_abi_bad_corpus():
    findings = _abi_check({"pkg/bind.py": ABI_BAD_PY}, ABI_CPP)
    msgs = "\n".join(f.message for f in findings)
    assert "argtypes declares 2 parameter(s) but the C signature " \
           "has 3" in msgs
    assert "called with 4 argument(s)" in msgs
    assert "`cshim_pull` returns C `long`" in msgs          # restype gap
    assert "`cshim_gone` is bound/called here but no extern" in msgs
    assert "`cshim_quiet` is never bound or called" in msgs  # dead ABI


def test_abi_good_corpus():
    assert _abi_check({"pkg/bind.py": ABI_GOOD_PY}, ABI_CPP) == []


def test_abi_argtypes_type_drift():
    py = (
        "import ctypes\n"
        "lib = ctypes.CDLL('x.so')\n"
        "lib.cshim_ping.argtypes = [ctypes.c_uint32, ctypes.c_void_p,\n"
        "                           ctypes.c_double]\n"   # size_t != double
    )
    findings = _abi_check({"pkg/bind.py": py}, ABI_CPP)
    assert any("argtypes[2] is `c_double` but the C parameter is "
               "`size_t`" in f.message for f in findings)


def test_abi_cpp_side_allowlist():
    cpp = ("extern \"C\" {\n"
           "// ctlint: disable=abi-surface  # consumed by Envoy, not Python\n"
           "void cshim_proxy_only() {}\n"
           "}\n")
    index, _ = ProjectIndex.from_sources({})
    findings = abi_rule.check_abi(index, cpp_sources={"shim/x.cpp": cpp},
                                  extra_py={})
    assert findings == []


def test_abi_real_surface_nonvacuous():
    """The rule must see the real shim + capture codec symbols."""
    index, _ = ProjectIndex.from_tree(REPO_ROOT, ("cilium_tpu",))
    assert abi_rule.symbol_count(index) >= 15


# -- config-surface ---------------------------------------------------------

CFG_SRC = """\
import dataclasses
import os


@dataclasses.dataclass
class EngineConfig:
    bank_size: int = 128
    ghost_knob: int = 0


@dataclasses.dataclass
class Config:
    enable: bool = False
    engine: EngineConfig = dataclasses.field(
        default_factory=EngineConfig)

    @classmethod
    def from_env(cls, env=os.environ):
        cfg = cls()
        if "CILIUM_TPU_ENABLE" in env:
            cfg.enable = True
        if "CILIUM_TPU_TYPO" in env:
            cfg.enabel = True
        return cfg

    @classmethod
    def from_toml(cls, path):
        cfg = cls()
        data = {}
        if "stale_key" in data:
            cfg.enable = data["stale_key"]
        return cfg
"""

CFG_USER = """\
import os

FLAG = os.environ.get("CILIUM_TPU_SECRET_KNOB")


def use(cfg):
    return cfg.engine.bank_size and cfg.enable
"""

CFG_DOCS_FULL = {"docs/CONFIG.md":
                 "`enable` `engine` `bank_size` `ghost_knob` "
                 "CILIUM_TPU_ENABLE CILIUM_TPU_TYPO "
                 "CILIUM_TPU_SECRET_KNOB"}


def test_config_surface_bad_corpus():
    index, _ = ProjectIndex.from_sources(
        {"pkg/core/config.py": CFG_SRC, "pkg/use.py": CFG_USER})
    findings = cfg_rule.check_config(
        index, config_module="pkg.core.config",
        docs={"docs/CONFIG.md": "`enable` `engine` `bank_size` "
                                "CILIUM_TPU_ENABLE CILIUM_TPU_TYPO "
                                "CILIUM_TPU_STALE_DOC_VAR"})
    msgs = "\n".join(f.message for f in findings)
    assert "maps `CILIUM_TPU_TYPO` to `cfg.enabel`" in msgs
    assert "from_toml copies key `stale_key`" in msgs
    assert "`CILIUM_TPU_SECRET_KNOB` is read here but documented " \
           "nowhere" in msgs
    assert "docs mention env var `CILIUM_TPU_STALE_DOC_VAR`" in msgs
    assert "`engine.ghost_knob` is documented nowhere" in msgs
    assert "`engine.ghost_knob` is never read outside" in msgs


def test_config_surface_good_corpus():
    good_src = CFG_SRC.replace(
        "            cfg.enabel = True", "            cfg.enable = True"
    ).replace("    ghost_knob: int = 0\n", "").replace(
        '        if "stale_key" in data:\n'
        '            cfg.enable = data["stale_key"]\n',
        '        if "enable" in data:\n'
        '            cfg.enable = data["enable"]\n')
    index, _ = ProjectIndex.from_sources(
        {"pkg/core/config.py": good_src, "pkg/use.py": CFG_USER})
    findings = cfg_rule.check_config(
        index, config_module="pkg.core.config", docs=CFG_DOCS_FULL)
    assert findings == []


def test_config_surface_real_tree_nonvacuous():
    index, _ = ProjectIndex.from_tree(REPO_ROOT, ("cilium_tpu",))
    assert cfg_rule.field_count(index) >= 30


# -- disable allowlist ------------------------------------------------------

def test_disable_comment_honored():
    src = (
        "def f():\n"
        "    try:\n"
        "        g()\n"
        "    # ctlint: disable=swallowed-exception  # test fixture\n"
        "    except Exception:\n"
        "        pass\n"
    )
    assert _check({"pkg/m.py": src}, exc_rule.check) == []


def test_disable_without_justification_is_a_finding():
    src = "import os  # ctlint: disable=unused-import\n"
    index, _ = ProjectIndex.from_sources({"pkg/m.py": src})
    from cilium_tpu.analysis.core import _bare_disable_findings

    findings = _bare_disable_findings(index)
    assert len(findings) == 1
    assert findings[0].rule == "bare-disable"


# -- the shipped tree -------------------------------------------------------

_TREE_RUN = []


def _tree_run():
    """One full-tree run shared by the gate tests below (a full run
    costs ~20s of abstract interpretation; the stability test still
    performs its own second, independent run). Returns
    (findings, suppressed, timings-at-run-time)."""
    if not _TREE_RUN:
        from cilium_tpu.analysis.core import LAST_TIMINGS
        findings, suppressed = run(REPO_ROOT)
        _TREE_RUN.append((findings, suppressed, dict(LAST_TIMINGS)))
    return _TREE_RUN[0]


def test_shipped_tree_is_clean():
    """The `make lint` gate, from inside the suite: zero
    non-allowlisted findings across cilium_tpu/."""
    findings, _suppressed, _t = _tree_run()
    assert findings == [], "\n".join(f.format() for f in findings)


def test_lock_graph_is_nontrivial():
    """Guard against the lock analysis going vacuously quiet: the real
    tree must yield a meaningful lock set and acquisition edges."""
    from cilium_tpu.analysis.callgraph import Project

    index, errors = ProjectIndex.from_tree(REPO_ROOT, ("cilium_tpu",))
    assert not errors
    a = lock_rule._Analyzer(Project(index))
    assert len(a.kinds) >= 30
    edges = 0
    for _key, s in a.summaries.items():
        edges += sum(1 for held, _l, _k, _ln in s.acquires if held)
        edges += sum(1 for held, _c, _ln in s.calls if held)
    assert edges >= 10


def test_purity_entries_found_in_tree():
    """Same guard for the purity walk: the engine's jitted entry
    points must be discovered."""
    from cilium_tpu.analysis.callgraph import Project

    index, _ = ProjectIndex.from_tree(REPO_ROOT, ("cilium_tpu",))
    names = {getattr(fn, "name", "<lambda>")
             for _mi, fn in purity_rule.find_entries(Project(index))}
    assert "verdict_step" in names
    assert "verdict_step_capture" in names


# -- regression: the frame-kind fix in StreamClient -------------------------

def test_stream_client_drops_unknown_frame_kind(tmp_path):
    """ctlint frame-kind found StreamClient._recv_loop treating ANY
    non-END/ERROR kind as a verdict array. Pin the fix: an unknown
    kind is dropped and counted, and the following valid chunk still
    lands for the same seq."""
    from cilium_tpu.runtime.metrics import METRICS
    from cilium_tpu.runtime.service import recv_msg, send_msg
    from cilium_tpu.runtime.stream import (
        KIND_CHUNK,
        KIND_END,
        StreamClient,
        send_frame,
    )

    path = str(tmp_path / "s.sock")
    srv = socket.socket(socket.AF_UNIX, socket.SOCK_STREAM)
    srv.bind(path)
    srv.listen(1)

    def server():
        conn, _ = srv.accept()
        recv_msg(conn)  # stream_start handshake
        send_msg(conn, {"ok": True, "revision": 1})
        # unknown kind 9 first: must be dropped, not parsed as the
        # verdicts for seq 0
        send_frame(conn, 0, 9, b"\x07\x07\x07\x07")
        send_frame(conn, 0, KIND_CHUNK, bytes([1, 2, 5]))
        send_frame(conn, 1, KIND_END)
        conn.close()

    th = threading.Thread(target=server, daemon=True)
    th.start()
    before = METRICS.get("cilium_tpu_stream_unknown_frames_total")
    client = StreamClient(path, timeout=10.0)
    try:
        verdicts = client.result(0)
        assert list(verdicts) == [1, 2, 5]
        assert METRICS.get("cilium_tpu_stream_unknown_frames_total") \
            == before + 1
    finally:
        client.close()
        srv.close()
    th.join(timeout=10)


def test_cli_lint_subcommand_json(capsys):
    """`cilium-tpu lint --format json` exits 0 on the shipped tree and
    prints a well-formed report. A rule subset keeps this a CLI-face
    test (~3s) rather than a third full-tree gate —
    test_shipped_tree_is_clean and the stability test already run the
    whole catalog."""
    import json

    from cilium_tpu.cli import main

    rc = main(["lint", "--format", "json",
               "--rule", "wall-clock", "--rule", "unused-import"])
    out = capsys.readouterr().out
    report = json.loads(out)
    assert rc == 0
    assert report["count"] == 0
    assert report["findings"] == []
    assert report["suppressed"] >= 1


def test_cli_lint_exits_nonzero_on_findings(tmp_path, capsys):
    bad = tmp_path / "bad.py"
    bad.write_text("def f():\n    try:\n        g()\n"
                   "    except:\n        pass\n")
    from cilium_tpu.cli import main

    rc = main(["lint", "--root", str(tmp_path), "bad.py"])
    out = capsys.readouterr().out
    assert rc == 1
    assert "swallowed-exception" in out


def test_cli_lint_rule_filter(tmp_path, capsys):
    """`--rule <id>` (repeatable) runs a subset — the pre-commit
    face. A file with a swallowed exception passes when only
    unused-import is requested."""
    bad = tmp_path / "bad.py"
    bad.write_text("def f():\n    try:\n        g()\n"
                   "    except:\n        pass\n")
    from cilium_tpu.cli import main

    rc = main(["lint", "--root", str(tmp_path), "bad.py",
               "--rule", "unused-import"])
    assert rc == 0
    capsys.readouterr()
    rc = main(["lint", "--root", str(tmp_path), "bad.py",
               "--rule", "swallowed-exception"])
    assert rc == 1
    assert main(["lint", "--rule", "no-such-rule"]) == 2


def test_report_schema_and_stability():
    """CTLINT.json carries schema_version + per-rule timings_ms; the
    findings portion is byte-stable for a clean tree across runs
    (cache warm vs cold, parallel parse order)."""
    from cilium_tpu.analysis.core import SCHEMA_VERSION, render_json

    def snapshot():
        findings, suppressed = run(REPO_ROOT)
        return json.loads(render_json(findings, suppressed))

    # run A comes from the shared tree run (timings snapshotted when
    # it ran); run B is always fresh, so the byte-stability claim
    # still compares two independent full runs
    fa, sa, tims = _tree_run()
    a = json.loads(render_json(fa, sa, tims))
    b = snapshot()
    ta = a.pop("timings_ms"), b.pop("timings_ms")
    assert a == b
    assert a["schema_version"] == SCHEMA_VERSION
    assert a["findings"] == []
    # timings cover every rule module plus the parse stage and the
    # whole-run wall clock (rules run on a thread pool, so per-rule
    # times overlap and may sum past the wall)
    assert "parse" in ta[0]
    assert "shapes" in ta[0] and "recompile" in ta[0]
    assert "abi" in ta[0] and "configsurface" in ta[0]
    assert "threadsafety" in ta[0] and "wall" in ta[0]
    assert "devicedataflow" in ta[0]
    # the committed lint-latency budget is part of the stable report
    assert a["wall_budget_ms"] >= 1000


def test_ast_cache_roundtrip(tmp_path):
    """The content-hash AST cache must return the same analysis on a
    warm run and ignore a corrupted cache file wholesale."""
    pkg = tmp_path / "pkg"
    pkg.mkdir()
    (pkg / "m.py").write_text("import os\n")  # one unused-import
    cold, _ = run(str(tmp_path), targets=("pkg",))
    cache_file = tmp_path / ".ctlint_cache" / "ast.pkl"
    assert cache_file.exists()
    warm, _ = run(str(tmp_path), targets=("pkg",))
    assert [f.as_dict() for f in warm] == [f.as_dict() for f in cold]
    cache_file.write_bytes(b"not a pickle")
    broken, _ = run(str(tmp_path), targets=("pkg",))
    assert [f.as_dict() for f in broken] == [f.as_dict() for f in cold]


def test_changed_only_filters_findings(tmp_path, capsys):
    """--changed-only indexes the whole tree but reports only
    git-changed paths (here: a repo with one dirty bad file and one
    committed bad file)."""
    import subprocess

    def git(*args):
        subprocess.run(["git", "-C", str(tmp_path), *args],
                       check=True, capture_output=True)

    git("init")
    git("config", "user.email", "t@t")
    git("config", "user.name", "t")
    committed = tmp_path / "old.py"
    committed.write_text("def f():\n    try:\n        g()\n"
                         "    except:\n        pass\n")
    git("add", "old.py")
    git("commit", "-m", "x")
    dirty = tmp_path / "new.py"
    dirty.write_text("import os\n\n\ndef g():\n    return 1\n")
    from cilium_tpu.cli import main

    rc = main(["lint", "--root", str(tmp_path), "old.py", "new.py",
               "--changed-only"])
    out = capsys.readouterr().out
    assert rc == 1
    assert "new.py" in out and "unused-import" in out
    assert "old.py" not in out  # committed finding filtered


# -- regressions: defects the v2 families found in the shipped tree ---------

def test_capture_l7g_argtypes_declared():
    """abi-surface found ct_capture_write_l7g was the one symbol bound
    without argtypes (its calls hand-wrapped scalars; nothing checked
    the pointer marshaling). Pin the declaration — when the native
    codec is available at all."""
    from cilium_tpu.ingest import binary

    lib = binary._native()
    if lib is None:
        import pytest

        pytest.skip("native capture codec unavailable")
    assert lib.ct_capture_write_l7g.argtypes is not None
    assert len(lib.ct_capture_write_l7g.argtypes) == 10


def test_parallel_wrappers_are_memoized():
    """recompile-hazard found every shard_map wrapper in tp/ulysses/
    longscan was rebuilt per call (fresh closure → full re-trace per
    batch). Pin the fix: the factories are lru_cached per
    (mesh, axis[, block])."""
    from cilium_tpu.engine.longscan import _cp_step
    from cilium_tpu.parallel.tp import _tp_banked_step, _tp_step
    from cilium_tpu.parallel.ulysses import _ulysses_step

    for fn in (_tp_step, _tp_banked_step, _ulysses_step, _cp_step):
        assert hasattr(fn, "cache_info"), fn


def test_mesh_from_config_wires_parallel_section():
    """config-surface found the whole [parallel] section was dead —
    no code read data_axis/expert_axis/mesh_shape/use_expert_axis.
    mesh_from_config is the wiring; pin its semantics."""
    import pytest

    from cilium_tpu.core.config import Config, ParallelConfig
    from cilium_tpu.parallel.mesh import (
        mesh_from_config,
        mesh_from_root_config,
    )

    mesh = mesh_from_config(ParallelConfig())
    assert tuple(mesh.axis_names) == ("data",)
    cfg = Config()
    assert tuple(mesh_from_root_config(cfg).axis_names) == ("data",)
    bad = ParallelConfig(mesh_shape=(1, 1))  # 2 dims, 1 axis named
    with pytest.raises(ValueError):
        mesh_from_config(bad)


def test_metrics_endpoint_honors_enable_metrics():
    """config-surface found enable_metrics was a dead knob; it now
    gates the /v1/metrics scrape surface."""
    import tempfile

    from cilium_tpu.agent import Agent
    from cilium_tpu.core.config import Config
    from cilium_tpu.runtime.api import APIClient, APIServer

    with tempfile.TemporaryDirectory() as d:
        cfg = Config()
        cfg.enable_metrics = False
        cfg.configure_logging = False
        agent = Agent(cfg)
        sock = os.path.join(d, "api.sock")
        server = APIServer(agent, sock).start()
        try:
            client = APIClient(sock)
            status, body = client.request("GET", "/v1/metrics")
            assert status == 404
        finally:
            server.stop()


# -- unbounded-queue --------------------------------------------------------

from cilium_tpu.analysis import queues as queue_rule  # noqa: E402

QUEUE_BAD = """\
import queue
import threading


class Pipeline:
    def __init__(self):
        self.q = queue.Queue()
        self._pending = []

    def start(self):
        self._t = threading.Thread(target=self._run)
        self._t.start()

    def submit(self, item):
        self._pending.append(item)

    def _run(self):
        pass
"""

QUEUE_GOOD = """\
import queue
import threading


class Pipeline:
    def __init__(self, bound):
        self.q = queue.Queue(maxsize=bound)
        self.q2 = queue.Queue(8)
        self._pending = []
        self.max_pending = bound

    def start(self):
        self._t = threading.Thread(target=self._run)
        self._t.start()

    def submit(self, item):
        if len(self._pending) >= self.max_pending:
            return False
        self._pending.append(item)
        return True

    def _run(self):
        pass
"""


def test_unbounded_queue_bad_corpus():
    findings = _check({"pkg/pipe.py": QUEUE_BAD}, queue_rule.check)
    msgs = "\n".join(f.message for f in findings)
    assert all(f.rule == "unbounded-queue" for f in findings)
    assert "`Queue()` without `maxsize`" in msgs
    assert "_pending" in msgs and "list used as a queue" in msgs
    assert len(findings) == 2


def test_unbounded_queue_good_corpus():
    assert _check({"pkg/pipe.py": QUEUE_GOOD},
                  queue_rule.check) == []


def test_unbounded_queue_scoping_and_forms():
    # no threading import → out of scope (single-threaded scripts may
    # use lists freely)
    single = QUEUE_BAD.replace("import threading\n", "") \
        .replace("self._t = threading.Thread(target=self._run)\n"
                 "        self._t.start()", "pass")
    assert _check({"pkg/single.py": single}, queue_rule.check) == []
    # `from queue import Queue` resolves through module imports
    src = ("from queue import Queue\n"
           "import threading\n\n\n"
           "def build():\n"
           "    return Queue()\n")
    findings = _check({"pkg/q.py": src}, queue_rule.check)
    assert len(findings) == 1 and findings[0].rule == "unbounded-queue"
    # LifoQueue/PriorityQueue count too
    src2 = ("import queue\nimport threading\n\n\n"
            "def build():\n"
            "    return queue.PriorityQueue()\n")
    assert len(_check({"pkg/q2.py": src2}, queue_rule.check)) == 1


def test_unbounded_queue_disable_pragma_honored():
    src = QUEUE_BAD.replace(
        "        self._pending.append(item)",
        "        # ctlint: disable=unbounded-queue  # test-only log\n"
        "        self._pending.append(item)").replace(
        "        self.q = queue.Queue()",
        "        # ctlint: disable=unbounded-queue  # drained inline\n"
        "        self.q = queue.Queue()")
    assert _check({"pkg/pipe.py": src}, queue_rule.check) == []


# -- obs-doc-parity ---------------------------------------------------------

from cilium_tpu.analysis import obsdocs as obs_rule  # noqa: E402

OBS_METRICS = '''\
FOO = "cilium_tpu_foo_total"

METRICS.describe(FOO, "foo events")
METRICS.describe("cilium_tpu_bar_seconds", "bar latency")
'''

OBS_TRACING = '''\
PHASE_QUEUE = "queue-wait"
PHASE_DEVICE = "device-dispatch"
'''

OBS_PHASES = '''\
ENGINE_PHASES = ("mapstate", "dfa-scan")
CAPTURE_PHASES = ("gather",)
'''

OBS_SOURCES = {
    "cilium_tpu/runtime/metrics.py": OBS_METRICS,
    "cilium_tpu/runtime/tracing.py": OBS_TRACING,
    "cilium_tpu/engine/phases.py": OBS_PHASES,
}

OBS_DOC_COMPLETE = (
    "catalog: `cilium_tpu_foo_total` and `cilium_tpu_bar_seconds`.\n"
    "phases: queue-wait, device-dispatch, mapstate, dfa-scan, "
    "gather, tables\n")


def test_obs_doc_parity_complete_doc_is_clean():
    assert _check(OBS_SOURCES, obs_rule.check_obs_docs,
                  doc_text=OBS_DOC_COMPLETE) == []


def test_obs_doc_parity_flags_undocumented_family_and_phase():
    doc = "only `cilium_tpu_foo_total` and queue-wait, mapstate, " \
          "dfa-scan, gather documented"
    findings = _check(OBS_SOURCES, obs_rule.check_obs_docs,
                      doc_text=doc)
    msgs = [f.message for f in findings]
    assert any("cilium_tpu_bar_seconds" in m for m in msgs)
    assert any("device-dispatch" in m for m in msgs)
    # undocumented-family findings anchor at the declaration
    fam = [f for f in findings if "bar_seconds" in f.message]
    assert fam[0].path == "cilium_tpu/runtime/metrics.py"


def test_obs_doc_parity_flags_stale_doc_name():
    doc = OBS_DOC_COMPLETE + \
        "\nand the long-gone `cilium_tpu_ghost_total` series\n"
    findings = _check(OBS_SOURCES, obs_rule.check_obs_docs,
                      doc_text=doc)
    assert len(findings) == 1
    assert "ghost" in findings[0].message
    assert findings[0].path.endswith("OBSERVABILITY.md")


def test_obs_doc_parity_derived_suffixes_are_fine():
    doc = OBS_DOC_COMPLETE + \
        "\nhistogram faces: cilium_tpu_bar_seconds_bucket and " \
        "cilium_tpu_bar_seconds_count\n"
    assert _check(OBS_SOURCES, obs_rule.check_obs_docs,
                  doc_text=doc) == []


def test_obs_doc_parity_stage_phase_literals_are_collected():
    sources = dict(OBS_SOURCES)
    sources["cilium_tpu/engine/verdict.py"] = (
        "class _StagePhase:\n"
        "    def __init__(self, phase):\n"
        "        self.phase = phase\n\n\n"
        "def stage():\n"
        "    with _StagePhase(\"tables\"):\n"
        "        pass\n")
    doc_missing = OBS_DOC_COMPLETE.replace(", tables", "")
    findings = _check(sources, obs_rule.check_obs_docs,
                      doc_text=doc_missing)
    assert any("`tables`" in f.message for f in findings)
    assert _check(sources, obs_rule.check_obs_docs,
                  doc_text=OBS_DOC_COMPLETE) == []


# -- obs-doc-parity: reason-label values (ISSUE 14) --------------------------

OBS_ADMISSION = '''\
SHED_QUEUE_FULL = "queue-full"
SHED_FAULT = "fault"
'''

OBS_MEMO = '''\
INVALIDATION_REASONS = ("policy-swap", "auth-change")


class M:
    def drop(self):
        self.invalidate("policy-swap")
'''

OBS_LABELS = '''\
def record(ok):
    METRICS.inc("cilium_tpu_foo_total",
                labels={"result": "hit" if ok else "miss"})
'''

REASON_SOURCES = {
    **OBS_SOURCES,
    "cilium_tpu/runtime/admission.py": OBS_ADMISSION,
    "cilium_tpu/engine/memo.py": OBS_MEMO,
    "cilium_tpu/runtime/checkpoint.py": OBS_LABELS,
}

REASON_DOC = OBS_DOC_COMPLETE + (
    "\n## Reason-label catalog\n\n"
    "| value | series | meaning |\n|---|---|---|\n"
    "| `queue-full` | shed | queue at bound |\n"
    "| `fault` | shed | armed fault fired |\n"
    "| `policy-swap` | memo | full drop |\n"
    "| `auth-change` | memo | auth view changed |\n"
    "| `hit` | fetches | served from store |\n"
    "| `miss` | fetches | not present |\n"
    "\n## after\n")


def test_reason_labels_complete_catalog_is_clean():
    assert _check(REASON_SOURCES, obs_rule.check_obs_docs,
                  doc_text=REASON_DOC) == []


def test_reason_labels_flag_undocumented_value():
    doc = REASON_DOC.replace("| `miss` | fetches | not present |\n",
                             "")
    findings = _check(REASON_SOURCES, obs_rule.check_obs_docs,
                      doc_text=doc)
    assert len(findings) == 1
    assert "`miss`" in findings[0].message
    # anchored at the emitting call site
    assert findings[0].path == "cilium_tpu/runtime/checkpoint.py"


def test_reason_labels_flag_stale_catalog_row():
    doc = REASON_DOC.replace(
        "| `miss` | fetches | not present |",
        "| `miss` | fetches | not present |\n"
        "| `long-gone` | shed | retired reason |")
    findings = _check(REASON_SOURCES, obs_rule.check_obs_docs,
                      doc_text=doc)
    assert len(findings) == 1
    assert "`long-gone`" in findings[0].message
    assert findings[0].path.endswith("OBSERVABILITY.md")


def test_reason_labels_only_catalog_section_rows_count():
    """Backticked tokens OUTSIDE the catalog section are not parsed
    as documented reason values (prose mentioning `zap` is not a
    catalog row), and rows after the next header don't count."""
    doc = REASON_DOC + "\nprose about a `zap` label value\n"
    assert _check(REASON_SOURCES, obs_rule.check_obs_docs,
                  doc_text=doc) == []


def test_reason_labels_real_tree_nonvacuous():
    """The shipped tree emits ≥12 distinct reason-label values (shed
    reasons + memo invalidation reasons + artifact fetch results +
    provenance results) and the shipped catalog covers every one."""
    import os

    from cilium_tpu.analysis.callgraph import Project

    index, errors = ProjectIndex.from_tree(REPO_ROOT)
    assert not errors
    values = obs_rule._reason_values(Project(index))
    assert len(values) >= 12, sorted(values)
    for expected in ("queue-full", "ring-full", "policy-swap",
                     "bank-swap", "hit", "corrupt", "explained",
                     "unexplained"):
        assert expected in values, expected
    with open(os.path.join(REPO_ROOT, "docs", "OBSERVABILITY.md"),
              encoding="utf-8") as fp:
        documented = obs_rule._documented_reasons(fp.read())
    assert set(values) <= set(documented)


# -- obs-doc-parity: program span catalog ------------------------------------

OBS_SPANS = '''\
def serve(lease):
    with TRACER.span("serve.submit", phase="host-prep"):
        pass
    with span("serve.cycle"):
        with host_sync("serve.readback"):
            pass
    with _StagePhase("tables"):
        pass
    with other.span("not.a.program.span"):
        pass
'''

SPAN_DOC = OBS_DOC_COMPLETE + (
    "\n## Program span catalog\n\n"
    "| span | thread | covers | read by |\n|---|---|---|---|\n"
    "| `cilium_tpu.serve.submit` | worker | submit | — |\n"
    "| `cilium_tpu.serve.cycle` | pack | a cycle | gap p99 |\n"
    "| `cilium_tpu.serve.readback.wait` | pack | readback | syncs |\n"
    "| `cilium_tpu.capture.stage.tables` | replay | tables | — |\n"
    "\n## after\n")


def _span_sources():
    tracing = OBS_TRACING + "\n\ndef span(name):\n    return name\n"
    return {**OBS_SOURCES, "cilium_tpu/runtime/tracing.py": tracing,
            "cilium_tpu/runtime/serveloop.py": OBS_SPANS}


def test_span_catalog_complete_is_clean():
    assert _check(_span_sources(), obs_rule.check_obs_docs,
                  doc_text=SPAN_DOC) == []


@pytest.mark.parametrize("row,name", [
    ("| `cilium_tpu.serve.cycle` | pack | a cycle | gap p99 |\n",
     "cilium_tpu.serve.cycle"),
    ("| `cilium_tpu.serve.readback.wait` | pack | readback | syncs |\n",
     "cilium_tpu.serve.readback.wait"),
    ("| `cilium_tpu.capture.stage.tables` | replay | tables | — |\n",
     "cilium_tpu.capture.stage.tables"),
])
def test_span_catalog_flags_span_without_row(row, name):
    findings = _check(_span_sources(), obs_rule.check_obs_docs,
                      doc_text=SPAN_DOC.replace(row, ""))
    assert len(findings) == 1, [f.message for f in findings]
    assert f"`{name}`" in findings[0].message
    assert findings[0].path == "cilium_tpu/runtime/serveloop.py"


def test_span_catalog_flags_stale_row():
    doc = SPAN_DOC.replace(
        "| `cilium_tpu.serve.submit` | worker | submit | — |",
        "| `cilium_tpu.serve.submit` | worker | submit | — |\n"
        "| `cilium_tpu.serve.gone.wait` | pack | retired | — |")
    findings = _check(_span_sources(), obs_rule.check_obs_docs,
                      doc_text=doc)
    assert len(findings) == 1
    assert "serve.gone.wait" in findings[0].message
    assert findings[0].path.endswith("OBSERVABILITY.md")


def test_span_catalog_real_tree_nonvacuous():
    """Both hot paths' spans are collected from the shipped tree, and
    the shipped catalog lists exactly them."""
    import os

    from cilium_tpu.analysis.callgraph import Project

    index, errors = ProjectIndex.from_tree(REPO_ROOT)
    assert not errors
    names = obs_rule._span_names(Project(index))
    for expected in ("stream.chunk", "serve.ticket.wait", "serve.cycle",
                     "serve.readback.wait", "capture.readback.wait",
                     "capture.stage.featurize", "ring.session_lock.wait"):
        assert expected in names, expected
    with open(os.path.join(REPO_ROOT, "docs", "OBSERVABILITY.md"),
              encoding="utf-8") as fp:
        rows = obs_rule._documented_rows(
            fp.read(), obs_rule.SPAN_SECTION, obs_rule._SPAN_ROW_RE)
    assert set(rows) == set(names)


def test_obs_doc_parity_real_tree_nonvacuous():
    """The shipped tree: ≥60 declared families, ≥10 phase labels, and
    the shipped doc covers them all (the rule would bite on drift)."""
    from cilium_tpu.analysis.callgraph import Project

    index, errors = ProjectIndex.from_tree(REPO_ROOT)
    assert not errors
    project = Project(index)
    families = obs_rule._declared_families(project)
    phases = obs_rule._phase_values(project)
    assert len(families) >= 60, len(families)
    assert len(phases) >= 10, sorted(phases)
    assert "tables" in phases and "dfa-scan" in phases
    assert obs_rule.check_obs_docs(index) == []


# -- pallas-block-shape ------------------------------------------------------

from cilium_tpu.analysis import pallas_shapes as pallas_rule  # noqa: E402

PALLAS_BAD = """\
import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

TILE = 100


def _kern(x_ref, o_ref):
    o_ref[:] = jnp.dot(x_ref[:], x_ref[:])


def run(x):
    return pl.pallas_call(
        _kern,
        grid=(1,),
        in_specs=[pl.BlockSpec((64, 100), lambda i: (0, 0))],
        out_specs=pl.BlockSpec((12, TILE), lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((12, 100), jnp.float32),
    )(x)
"""

PALLAS_GOOD = """\
import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

TILE = 1024


def _kern(x_ref, o_ref):
    o_ref[:] = jnp.dot(x_ref[:], x_ref[:],
                       preferred_element_type=jnp.float32)


def run(x, L):
    return pl.pallas_call(
        _kern,
        grid=(1,),
        in_specs=[pl.BlockSpec((1, L, TILE), lambda i: (0, 0, 0)),
                  pl.BlockSpec((8, 128), lambda i: (0, 0))],
        out_specs=pl.BlockSpec((1, 1, 8, 128), lambda i: (0, 0, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((8, 128), jnp.float32),
    )(x)
"""


def test_pallas_block_shape_bad_corpus():
    findings = _check({"pkg/k.py": PALLAS_BAD}, pallas_rule.check)
    assert all(f.rule == "pallas-block-shape" for f in findings)
    msgs = "\n".join(f.message for f in findings)
    # 100 violates the 128-lane tile twice (literal + via TILE const),
    # 12 violates the 8-sublane tile, and the kernel dot is unpinned
    assert msgs.count("not a multiple of 128") == 2
    assert "not a multiple of 8" in msgs
    assert "preferred_element_type" in msgs
    assert len(findings) == 4


def test_pallas_block_shape_good_corpus():
    # aligned literals, module constants, variable dims (not guessed),
    # leading size-1 dims, and a pinned dot: all clean
    assert _check({"pkg/k.py": PALLAS_GOOD}, pallas_rule.check) == []


def test_pallas_block_shape_dot_outside_kernel_not_flagged():
    src = PALLAS_GOOD.replace(
        "def run(x, L):",
        "def helper(a, b):\n"
        "    return jnp.dot(a, b)\n\n\n"
        "def run(x, L):")
    # an unpinned dot in a NON-kernel function is host/XLA code where
    # the default precision rules apply — out of this rule's scope
    assert _check({"pkg/k.py": src}, pallas_rule.check) == []


def test_pallas_block_shape_shipped_kernels_clean():
    src_dfa = open(os.path.join(
        REPO_ROOT, "cilium_tpu/engine/pallas_dfa.py")).read()
    src_nfa = open(os.path.join(
        REPO_ROOT, "cilium_tpu/engine/pallas_nfa.py")).read()
    assert _check({"cilium_tpu/engine/pallas_dfa.py": src_dfa,
                   "cilium_tpu/engine/pallas_nfa.py": src_nfa},
                  pallas_rule.check) == []


# ---------------------------------------------------------------------------
# wall-clock (behavioral time routes through the injected Clock)

from cilium_tpu.analysis import wallclock as wc_rule  # noqa: E402

WALLCLOCK_BAD = """
import time


class Breaker:
    def __init__(self):
        self.opened_at = time.monotonic()

    def expired(self):
        return time.time() > self.opened_at + 5.0

    def backoff(self):
        time.sleep(0.5)
"""

WALLCLOCK_GOOD = """
import time

from cilium_tpu.runtime import simclock


class Breaker:
    def __init__(self):
        self.opened_at = simclock.now()

    def expired(self):
        return simclock.wall() > self.opened_at + 5.0

    def backoff(self):
        simclock.sleep(0.5)

    def measure(self):
        # perf_counter is measurement, exempt by design
        t0 = time.perf_counter()
        return time.perf_counter() - t0
"""


def test_wall_clock_bad_corpus_flags_all_three_surfaces():
    findings = _check({"cilium_tpu/runtime/breaker.py": WALLCLOCK_BAD},
                      wc_rule.check)
    assert all(f.rule == "wall-clock" for f in findings)
    msgs = "\n".join(f.message for f in findings)
    assert "time.monotonic" in msgs
    assert "time.time" in msgs
    assert "time.sleep" in msgs
    assert len(findings) == 3


def test_wall_clock_good_corpus_clean_and_perf_counter_exempt():
    assert _check({"cilium_tpu/runtime/breaker.py": WALLCLOCK_GOOD},
                  wc_rule.check) == []


def test_wall_clock_out_of_scope_modules_untouched():
    # analysis/bench/cli modules are NOT serving-plane scope; the
    # clock seam itself is explicitly exempt
    for path in ("cilium_tpu/analysis/timing.py",
                 "cilium_tpu/cli.py",
                 "cilium_tpu/runtime/simclock.py"):
        assert _check({path: WALLCLOCK_BAD}, wc_rule.check) == [], path


def test_wall_clock_justified_disable_honored():
    src = WALLCLOCK_BAD.replace(
        "        self.opened_at = time.monotonic()",
        "        # ctlint: disable=wall-clock  # capture stamp of the real world\n"
        "        self.opened_at = time.monotonic()")
    findings = _check({"cilium_tpu/runtime/breaker.py": src},
                      wc_rule.check)
    assert len(findings) == 2  # the allowlisted monotonic is gone


def test_wall_clock_from_import_alias_flagged():
    src = """
from time import sleep


def retry():
    sleep(1.0)
"""
    findings = _check({"cilium_tpu/runtime/retry.py": src},
                      wc_rule.check)
    assert len(findings) == 1
    assert "time.sleep" in findings[0].message


def test_wall_clock_tree_is_clean():
    """The refactor is COMPLETE: the shipped serving plane has no
    unjustified direct clock reads (the tree-wide acceptance)."""
    findings, _sup = run(REPO_ROOT, rules=["wall-clock"])
    assert findings == [], "\n".join(f.format() for f in findings)


# -- unbounded-registry -----------------------------------------------------

from cilium_tpu.analysis import unboundedreg as ureg_rule  # noqa: E402

UREG_BAD = """\
from typing import Dict

SEEN: Dict[str, int] = {}


def on_request(key, value):
    SEEN[key] = value


class Registry:
    def __init__(self):
        self._by_key = {}
        self._members = set()

    def on_event(self, key, value):
        self._by_key[key] = value
        self._members.add(key)
"""

UREG_GOOD = """\
from typing import Dict

TABLE: Dict[str, int] = {}


def on_request(key, value):
    TABLE[key] = value
    if len(TABLE) > 1024:
        TABLE.clear()


class Registry:
    def __init__(self):
        self._by_key = {}
        self._lru = {}
        self._rebuilt = {}
        self.max_entries = 64

    def on_event(self, key, value):
        if len(self._by_key) >= self.max_entries:
            self._by_key.pop(next(iter(self._by_key)))
        self._by_key[key] = value
        self._lru[key] = value

    def evict(self, key):
        del self._lru[key]

    def prune(self, live):
        self._rebuilt = {k: v for k, v in self._rebuilt.items()
                         if k in live}

    def insert(self, k, v):
        self._rebuilt[k] = v
"""


def test_unbounded_registry_bad_corpus():
    findings = _check({"cilium_tpu/runtime/reg.py": UREG_BAD},
                      ureg_rule.check)
    assert all(f.rule == "unbounded-registry" for f in findings)
    msgs = "\n".join(f.message for f in findings)
    assert "SEEN" in msgs and "_by_key" in msgs and "_members" in msgs
    assert len(findings) == 3


def test_unbounded_registry_good_corpus():
    assert _check({"cilium_tpu/runtime/reg.py": UREG_GOOD},
                  ureg_rule.check) == []


def test_unbounded_registry_scoped_to_longlived_modules():
    # the same bad source OUTSIDE runtime/engine/policy is out of
    # scope (CLI helpers, tests, benches may hold short-lived maps)
    assert _check({"cilium_tpu/ingest/reg.py": UREG_BAD},
                  ureg_rule.check) == []
    assert _check({"cilium_tpu/engine/reg.py": UREG_BAD},
                  ureg_rule.check) != []
    assert _check({"cilium_tpu/policy/compiler/reg.py": UREG_BAD},
                  ureg_rule.check) != []


def test_unbounded_registry_init_time_insertion_not_flagged():
    src = (
        "class Warm:\n"
        "    def __init__(self, pairs):\n"
        "        self._by_key = {}\n"
        "        for k, v in pairs:\n"
        "            self._by_key[k] = v\n")
    assert _check({"cilium_tpu/runtime/w.py": src},
                  ureg_rule.check) == []


def test_unbounded_registry_disable_pragma_honored():
    src = UREG_BAD.replace(
        "    SEEN[key] = value",
        "    # ctlint: disable=unbounded-registry  # bounded upstream\n"
        "    SEEN[key] = value").replace(
        "        self._by_key[key] = value",
        "        # ctlint: disable=unbounded-registry  # test corpus\n"
        "        self._by_key[key] = value").replace(
        "        self._members.add(key)",
        "        # ctlint: disable=unbounded-registry  # test corpus\n"
        "        self._members.add(key)")
    assert _check({"cilium_tpu/runtime/reg.py": src},
                  ureg_rule.check) == []


def test_unbounded_registry_tree_clean():
    """The shipped tree passes with justified allowlists only — the
    fleet-scale stores (sharded registry, fingerprint store, artifact
    LRU) all carry real bounds."""
    from cilium_tpu.analysis.core import run as _ctrun

    findings, _ = _ctrun(REPO_ROOT, rules=["unbounded-registry"])
    assert findings == [], [str(f) for f in findings]


# -------------------------------------------------- frontend-registry --

from cilium_tpu.analysis import frontendreg as fereg_rule  # noqa: E402

FEREG_FLOW = (
    "import enum\n"
    "class L7Type(enum.IntEnum):\n"
    "    NONE = 0\n"
    "    HTTP = 1\n"
    "    KAFKA = 2\n"
    "    DNS = 3\n"
    "    GENERIC = 4\n"
    "    CASS = 5\n")

FEREG_MEMO = (
    'FAMILY_OF_L7TYPE = {0: "l4", 1: "http", 2: "kafka", 3: "dns",\n'
    '                    4: "generic", 5: "cass"}\n')

FEREG_ATTR = (
    "from cilium_tpu.core.flow import L7Type\n"
    'FAMILY_NAMES = {int(L7Type.HTTP): "http",\n'
    '                int(L7Type.CASS): "cass"}\n')

FEREG_SPEC = (
    "from cilium_tpu.policy.compiler.frontends import (\n"
    "    FrontendSpec, ProtocolFrontend, register_frontend)\n"
    "class CassFe(ProtocolFrontend):\n"
    "    spec = FrontendSpec(name='cass', family=5,\n"
    "                        family_name='cass', fields=('q',))\n"
    "register_frontend(CassFe())\n")

FEREG_PARSERS = (
    "from cilium_tpu.proxylib.parser import register_parser\n"
    "class P: pass\n"
    "register_parser('cass', P)\n")


def _fereg_corpus(**over):
    base = {
        "cilium_tpu/core/flow.py": FEREG_FLOW,
        "cilium_tpu/engine/memo.py": FEREG_MEMO,
        "cilium_tpu/engine/attribution.py": FEREG_ATTR,
        "cilium_tpu/policy/compiler/frontends/cass.py": FEREG_SPEC,
        "cilium_tpu/proxylib/cass.py": FEREG_PARSERS,
    }
    base.update(over)
    return base


def test_frontend_registry_good_corpus():
    assert _check(_fereg_corpus(),
                  fereg_rule.check_frontend_registry) == []


def test_frontend_registry_parser_without_frontend():
    bad = FEREG_PARSERS + "register_parser('loose', P)\n"
    findings = _check(_fereg_corpus(**{
        "cilium_tpu/proxylib/cass.py": bad}),
        fereg_rule.check_frontend_registry)
    assert len(findings) == 1
    assert "loose" in findings[0].message
    assert "proxy-only" in findings[0].message
    # ...and the justified pragma allowlists it
    ok = FEREG_PARSERS + ("register_parser('loose', P)"
                          "  # ctlint: disable=frontend-registry"
                          "  # proxy-only fixture\n")
    assert _check(_fereg_corpus(**{
        "cilium_tpu/proxylib/cass.py": ok}),
        fereg_rule.check_frontend_registry) == []


def test_frontend_registry_family_missing_from_memo_enum():
    memo = FEREG_MEMO.replace(', 5: "cass"', "")
    findings = _check(_fereg_corpus(**{
        "cilium_tpu/engine/memo.py": memo}),
        fereg_rule.check_frontend_registry)
    assert any("FAMILY_OF_L7TYPE" in f.message for f in findings)


def test_frontend_registry_family_missing_from_attribution():
    attr = ('from cilium_tpu.core.flow import L7Type\n'
            'FAMILY_NAMES = {int(L7Type.HTTP): "http"}\n')
    findings = _check(_fereg_corpus(**{
        "cilium_tpu/engine/attribution.py": attr}),
        fereg_rule.check_frontend_registry)
    assert any("FAMILY_NAMES" in f.message for f in findings)


def test_frontend_registry_family_missing_from_l7type():
    flow = FEREG_FLOW.replace("    CASS = 5\n", "")
    findings = _check(_fereg_corpus(**{
        "cilium_tpu/core/flow.py": flow}),
        fereg_rule.check_frontend_registry)
    assert any("L7Type" in f.message for f in findings)


def test_frontend_registry_frontend_without_parser():
    findings = _check(_fereg_corpus(**{
        "cilium_tpu/proxylib/cass.py": "x = 1\n"}),
        fereg_rule.check_frontend_registry)
    assert any("differential CPU oracle" in f.message
               for f in findings)


def test_frontend_registry_tree_clean():
    index, _ = ProjectIndex.from_tree(REPO_ROOT,
                                      targets=("cilium_tpu",))
    findings = [f for f in
                fereg_rule.check_frontend_registry(index)
                if not index.by_path[f.path].disabled(f.line, f.rule)]
    assert findings == [], [f.format() for f in findings]
    # non-vacuity: the shipped tree declares >= 3 frontends and >= 5
    # parser registrations the rule actually walked
    assert len(fereg_rule._frontend_specs(index)) >= 3
    assert len(fereg_rule._parser_registrations(index)) >= 5


# -- thread-safety (v3) -----------------------------------------------------

from cilium_tpu.analysis import threadsafety as ts_rule  # noqa: E402

CORPUS_DIR = os.path.join(REPO_ROOT, "tests", "analysis_corpus")


def _corpus(name):
    with open(os.path.join(CORPUS_DIR, name)) as fp:
        return fp.read()


def _ts_check_file(name):
    """Run the thread-safety rule over ONE corpus file, placed under
    the rule's default scope (cilium_tpu/runtime/)."""
    return _check({f"cilium_tpu/runtime/{name}": _corpus(name)},
                  ts_rule.check)


def test_thread_safety_bad_corpus_catches_prefix_races():
    """The five pre-fix PR-11 race reconstructions: the rule must
    keep catching at least four of the five (the acceptance floor —
    today it catches all five)."""
    bad = ["race_counter_bad.py", "race_lease_act_bad.py",
           "race_reinsert_bad.py", "race_publication_bad.py",
           "race_dispatch_bad.py"]
    caught = [n for n in bad if _ts_check_file(n)]
    assert len(caught) >= 4, f"only caught {caught}"


def test_thread_safety_good_corpus_clean():
    """Every fixed counterpart — the shape the real fix took — must
    be quiet."""
    for name in ["race_counter_good.py", "race_lease_act_good.py",
                 "race_reinsert_good.py", "race_publication_good.py",
                 "race_dispatch_good.py"]:
        assert _ts_check_file(name) == [], name


def test_thread_safety_guard_inference_names_racing_roots():
    """Majority-guard inference: the unlocked `connect` bump is
    flagged (2/3 sites locked) and the finding names two distinct
    racing roots — the public caller and the pack thread."""
    out = _ts_check_file("race_counter_bad.py")
    guarded = [f for f in out if "guarded by" in f.message]
    assert len(guarded) == 1
    f = guarded[0]
    assert "2/3 mutation sites" in f.message
    assert len(f.roots) == 2
    assert any(r.startswith("thread:") for r in f.roots)
    assert f.as_dict()["roots"] == list(f.roots)
    # and the bare += with no lock anywhere is its own finding
    assert any("read-modify-write" in f.message for f in out)


def test_thread_safety_check_then_act():
    out = _ts_check_file("race_lease_act_bad.py")
    assert any("check-then-act" in f.message and "`lease`" in f.message
               for f in out)


def test_thread_safety_release_window_and_revalidation_idiom():
    """The blind write-back is a lock-release window; re-validating
    the key under the lock before the write (the fixed idiom) is
    recognized and suppresses it."""
    out = _ts_check_file("race_reinsert_bad.py")
    assert any("lock-release window" in f.message for f in out)
    assert _ts_check_file("race_reinsert_good.py") == []


def test_thread_safety_publication():
    out = _ts_check_file("race_publication_bad.py")
    assert any("unsafe publication" in f.message for f in out)


def test_thread_safety_out_of_scope_modules_untouched():
    """The rule only reports inside the serving fleet's scope — the
    same racy source outside cilium_tpu/runtime/ stays quiet."""
    src = _corpus("race_counter_bad.py")
    assert _check({"cilium_tpu/hubble/race_counter_bad.py": src},
                  ts_rule.check) == []
    # ...unless a test overrides the scope explicitly
    assert _check({"cilium_tpu/hubble/race_counter_bad.py": src},
                  ts_rule.check, scope=("cilium_tpu/hubble/",)) != []


def test_thread_safety_disable_pragma_honored():
    # the finding anchors on the first late assign, so the pragma's
    # comment-only line goes right above it
    src = _corpus("race_publication_bad.py").replace(
        "        self._pending = {}",
        "        # ctlint: disable=thread-safety  # corpus fixture\n"
        "        self._pending = {}")
    out = _check({"cilium_tpu/runtime/race_publication_bad.py": src},
                 ts_rule.check)
    assert not any("unsafe publication" in f.message for f in out)


def _real_tree_index():
    """One shared tree index for the real-tree thread-safety tests
    (project/analyzer memoize onto it, so building it once keeps
    these tests off the suite's wall-time budget)."""
    global _TS_TREE_INDEX
    if _TS_TREE_INDEX is None:
        index, errors = ProjectIndex.from_tree(REPO_ROOT,
                                               ("cilium_tpu",))
        assert not errors
        _TS_TREE_INDEX = index
    return _TS_TREE_INDEX


_TS_TREE_INDEX = None


def test_thread_safety_roots_nonvacuous():
    """Guard against root discovery going vacuously quiet: the real
    tree must yield a healthy set of concurrency roots (thread
    targets, executor submits, handler entries)."""
    from cilium_tpu.analysis.callgraph import project_for
    from cilium_tpu.analysis.locks import analyzer_for

    a = analyzer_for(project_for(_real_tree_index()))
    seeds = ts_rule.discover_roots(a)
    labels = set()
    for v in seeds.values():
        labels |= v
    assert len(seeds) >= 10, sorted(labels)
    assert any(lbl.startswith("thread:") for lbl in labels)
    assert any(lbl.startswith("executor:") for lbl in labels)
    reach = ts_rule.reachable_roots(a, seeds)
    assert len(reach) > len(seeds)


def test_thread_safety_tree_is_clean():
    """The serving fleet itself passes its own analysis (fixes +
    justified allowlists, never silent). Runs the one checker over
    the shared index — `make lint` and test_shipped_tree_is_clean
    already cover the full-run path."""
    index = _real_tree_index()
    findings = []
    for f in ts_rule.check(index):
        sf = index.by_path.get(f.path)
        if sf is not None and sf.disabled(f.line, f.rule):
            continue
        findings.append(f)
    assert findings == [], "\n".join(f.format() for f in findings)


def test_wall_budget_gate(tmp_path, capsys):
    """--wall-budget-ms: a generous budget passes, an impossible one
    fails the run even with zero findings (the make lint latency
    gate)."""
    from cilium_tpu.analysis import run_cli

    pkg = tmp_path / "pkg"
    pkg.mkdir()
    (pkg / "m.py").write_text("X = 1\n")
    argv = ["pkg", "--root", str(tmp_path)]
    assert run_cli(argv + ["--wall-budget-ms", "600000"]) == 0
    capsys.readouterr()
    assert run_cli(argv + ["--wall-budget-ms", "0"]) == 1
    assert "exceeds budget" in capsys.readouterr().err


# -- device-dataflow (v4) ---------------------------------------------------

from cilium_tpu.analysis import devicedataflow as dd_rule  # noqa: E402


def _dd_check_file(name):
    """Run the device-dataflow family over ONE corpus file, placed
    under the family's hot-path scope (cilium_tpu/engine/)."""
    return _check({f"cilium_tpu/engine/{name}": _corpus(name)},
                  dd_rule.check)


def test_device_sync_bad_corpus():
    """All three implicit-sync faces fire on the pre-fix shape: the
    truthiness branch, the float() scalar coercion, and the
    per-iteration np.asarray readback — and each finding carries the
    residency chain naming the dispatch that made the value
    device-resident."""
    out = _dd_check_file("device_sync_bad.py")
    sync = [f for f in out if f.rule == "implicit-sync"]
    assert len(sync) >= 3, out
    assert any("`truthiness`" in f.message for f in sync)
    assert any("`float()`" in f.message for f in sync)
    assert any("`np.asarray`" in f.message and "inside a loop"
               in f.message for f in sync)
    for f in sync:
        assert f.residency, f
        assert any("verdict_step" in r for r in f.residency), f


def test_device_sync_good_clean():
    """Dispatch everything, then one batched device_get at the edge:
    the documented API-edge contract is quiet."""
    assert _dd_check_file("device_sync_good.py") == []


def test_device_h2d_bad_and_prefetch_suppression():
    """Per-iteration device_put in the replay loop is flagged; the
    PR-7 double-buffer idiom (staged store into instance state) is
    recognized and suppressed."""
    out = _dd_check_file("device_h2d_bad.py")
    assert any(f.rule == "hot-loop-h2d" and "`device_put`"
               in f.message for f in out), out
    assert _dd_check_file("device_h2d_good.py") == []


def test_device_donation_bad_good():
    """The memo-refill shape — a jitted step overwriting its input
    table via .at[].set — must be flagged without donate_argnums and
    quiet with it."""
    out = _dd_check_file("device_donation_bad.py")
    assert any(f.rule == "missing-donation" and "`table`" in f.message
               and "donate_argnums=(0,)" in f.message for f in out), out
    assert _dd_check_file("device_donation_good.py") == []


def test_device_readback_ordering_bad_good():
    """Reading A back before issuing independent dispatch B stalls
    the pipeline and is flagged at the readback site; issuing both
    dispatches then batching the readback is quiet."""
    out = _dd_check_file("device_readback_bad.py")
    order = [f for f in out if f.rule == "readback-ordering"]
    assert len(order) == 1, out
    assert "step_b" in order[0].message
    assert _dd_check_file("device_readback_good.py") == []


def test_device_findings_carry_residency_in_json():
    """schema_version-4: the residency provenance chain rides
    as_dict() so CTLINT.json consumers see WHY the value is
    device-resident."""
    out = _dd_check_file("device_sync_bad.py")
    assert out
    for f in out:
        d = f.as_dict()
        assert d["residency"] == list(f.residency)
        assert d["residency"]


def test_device_hot_root_discovery_nonvacuous():
    """The shipped tree's serving spine is discovered: well beyond
    the >=5 floor, and the named anchors are all present."""
    index = _real_tree_index()
    from cilium_tpu.analysis.callgraph import project_for

    labels = {label for _, _, _, label
              in dd_rule.find_hot_roots(project_for(index))}
    assert len(labels) >= 5, labels
    for want in ("cilium_tpu/engine/ring.py::VerdictRing.pack",
                 "cilium_tpu/engine/session.py::"
                 "IncrementalSession.serve_ids",
                 "cilium_tpu/engine/verdict.py::"
                 "CaptureReplay.verdict_chunk",
                 "cilium_tpu/runtime/serveloop.py::ServeLoop.step",
                 "cilium_tpu/fqdn/dnsproxy.py::DNSProxy.check_batch",
                 "cilium_tpu/engine/megakernel.py::fused_verdict_step",
                 "cilium_tpu/engine/attribution.py::ServedPack.host"):
        assert want in labels, want


def test_device_residency_survives_depth2_chain():
    """Residency tracks through two interprocedural hops: hot() gets
    its device value from middle() which gets it from stage()'s
    device_put — the finding's residency chain names the stage()
    def-site."""
    src = (
        "import jax\n"
        "import jax.numpy as jnp\n"
        "\n"
        "@jax.jit\n"
        "def step(x):\n"
        "    return x\n"
        "\n"
        "def stage(c):\n"
        "    return jax.device_put(c)\n"
        "\n"
        "def middle(c):\n"
        "    return stage(c)\n"
        "\n"
        "def hot(chunks):\n"
        "    dev = middle(chunks)\n"
        "    out = step(dev)\n"
        "    host = jax.device_get(out)\n"
        "    return float(dev), host\n")
    out = _check({"cilium_tpu/engine/chain.py": src}, dd_rule.check)
    sync = [f for f in out if f.rule == "implicit-sync"]
    assert len(sync) == 1, out
    f = sync[0]
    assert f.line == 18
    assert any("chain.py:9 device_put" in r for r in f.residency), f


def test_device_disable_honored():
    """The standard justified-allowlist syntax silences a device
    finding like any other rule's."""
    src = _corpus("device_sync_bad.py").replace(
        "    total = float(out)             # scalar coercion blocks again\n",
        "    # ctlint: disable=implicit-sync  # debug probe, not serving\n"
        "    total = float(out)\n")
    out = _check({"cilium_tpu/engine/device_sync_bad.py": src},
                 dd_rule.check)
    assert not any("`float()`" in f.message for f in out), out
    assert any("`truthiness`" in f.message for f in out)


def test_device_tree_is_clean():
    """The serving hot path passes its own device analysis (the PR-19
    batching/prefetch fixes + justified allowlists, never silent)."""
    index = _real_tree_index()
    findings = []
    for f in dd_rule.check(index):
        sf = index.by_path.get(f.path)
        if sf is not None and sf.disabled(f.line, f.rule):
            continue
        findings.append(f)
    assert findings == [], "\n".join(f.format() for f in findings)
