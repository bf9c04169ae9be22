"""Static analysis (parsec_tpu/analysis/ + tools/parsec_lint.py).

Golden-file tests: each deliberately-broken spec is caught with the
expected finding code; the shipped specs, examples, and the runtime
source produce ZERO gating findings (the tier-1 self-lint gate).
"""
import os
import subprocess
import sys

import pytest

from parsec_tpu.analysis import (Finding, body_check, gate, lock_check,
                                 ptg_check)
from parsec_tpu.dsl import ptg

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def codes(findings):
    return {f.code for f in findings}


def verify(text, **kw):
    kw.setdefault("cycles", False)
    return ptg_check.verify_jdf_text(text, name="golden", **kw)


# --------------------------------------------------------------------- #
# golden broken specs — the PTG dataflow verifier                        #
# --------------------------------------------------------------------- #
GOLDEN_DANGLING = """
NB [ type="int" ]
A(k)
k = 0 .. NB
RW X <- NEW  [ shape=1 ]
     -> X Nowhere( k )
BODY
pass
END
"""


def test_golden_dangling_endpoint():
    fs = verify(GOLDEN_DANGLING)
    assert "PTG101" in codes(fs), fs


GOLDEN_NONRECIPROCAL = """
c [ type="collection" ]
NB [ type="int" ]
A(k)
k = 0 .. NB
: c( k )
RW X <- c( k )
     -> X B( k )
BODY
pass
END

B(k)
k = 0 .. NB
: c( k )
RW X <- c( k )
     -> c( k )
BODY
pass
END
"""


def test_golden_non_reciprocal_dep():
    fs = verify(GOLDEN_NONRECIPROCAL)
    assert "PTG105" in codes(fs), fs
    # the finding names both endpoints of the one-sided edge
    msg = next(f.message for f in fs if f.code == "PTG105")
    assert "A.X" in msg and "B.X" in msg


GOLDEN_CTL_CYCLE = """
A(k)
k = 0 .. 1
CTL ctl <- ctl B( k )
        -> ctl B( k )
BODY
pass
END

B(k)
k = 0 .. 1
CTL ctl <- ctl A( k )
        -> ctl A( k )
BODY
pass
END
"""


def test_golden_ctl_cycle():
    fs = ptg_check.verify_jdf_text(GOLDEN_CTL_CYCLE, name="golden",
                                   cycles=True)
    assert "PTG109" in codes(fs), fs


GOLDEN_UNUSED_LOCAL = """
c [ type="collection" ]
NB [ type="int" ]
A(k)
k = 0 .. NB
j = k + 1
: c( k )
RW X <- c( k )
     -> c( k )
BODY
pass
END
"""


def test_golden_unused_local():
    fs = verify(GOLDEN_UNUSED_LOCAL)
    assert "PTG107" in codes(fs), fs
    assert any("'j'" in f.message for f in fs if f.code == "PTG107")


GOLDEN_WRITE_FEEDS_WRITE = """
c [ type="collection" ]
NB [ type="int" ]
A(k)
k = 0 .. NB
: c( k )
RW X <- c( k )
     -> S B( k )
BODY
pass
END

B(k)
k = 0 .. NB
: c( k )
WRITE S <- X A( k )
        -> c( k )
BODY
pass
END
"""


def test_golden_write_feeds_write():
    fs = verify(GOLDEN_WRITE_FEEDS_WRITE)
    assert "PTG103" in codes(fs), fs


GOLDEN_ARITY = """
c [ type="collection" ]
NB [ type="int" ]
A(k)
k = 0 .. NB
: c( k )
RW X <- c( k )
     -> X B( k, 0 )
BODY
pass
END

B(k)
k = 0 .. NB
: c( k )
RW X <- X A( k )
     -> c( k )
BODY
pass
END
"""


def test_golden_arity_mismatch():
    fs = verify(GOLDEN_ARITY)
    assert "PTG104" in codes(fs), fs


GOLDEN_UNSAT_GUARD = """
c [ type="collection" ]
NB [ type="int" ]
A(k)
k = 0 .. NB
: c( k )
RW X <- (k != k) ? c( k ) : NEW  [ shape=1 ]
     -> c( k )
BODY
pass
END
"""


def test_golden_unsatisfiable_guard():
    fs = verify(GOLDEN_UNSAT_GUARD)
    assert "PTG108" in codes(fs), fs


GOLDEN_CTL_DATA_MISMATCH = """
c [ type="collection" ]
NB [ type="int" ]
A(k)
k = 0 .. NB
: c( k )
RW X <- c( k )
     -> ctl B( k )
BODY
pass
END

B(k)
k = 0 .. NB
: c( k )
RW Y <- c( k )
     -> c( k )
CTL ctl <- X A( k )
BODY
pass
END
"""


def test_golden_ctl_data_mismatch():
    fs = verify(GOLDEN_CTL_DATA_MISMATCH)
    assert "PTG102" in codes(fs), fs


GOLDEN_UNUSED_GLOBAL = """
c [ type="collection" ]
NB [ type="int" ]
SPARE [ type="int" ]
A(k)
k = 0 .. NB
: c( k )
RW X <- c( k )
     -> c( k )
BODY
pass
END
"""


def test_golden_unused_global():
    fs = verify(GOLDEN_UNUSED_GLOBAL)
    assert "PTG106" in codes(fs), fs
    assert any("SPARE" in f.message for f in fs if f.code == "PTG106")


# --------------------------------------------------------------------- #
# golden broken bodies — the batch/donation-safety linter                #
# --------------------------------------------------------------------- #
GOLDEN_THIS_TASK = """
c [ type="collection" ]
NB [ type="int" ]
A(k)
k = 0 .. NB
: c( k )
RW X <- c( k )
     -> c( k )
BODY [type=tpu]
X = X + this_task.priority
END
"""


def test_golden_this_task_body():
    jdf = ptg.compile_jdf(GOLDEN_THIS_TASK, name="golden").jdf
    fs = body_check.check_jdf_bodies(jdf)
    assert "BDY201" in codes(fs), fs
    assert any("NEVER batches" in f.message for f in fs)


GOLDEN_UNTRACEABLE = """
c [ type="collection" ]
NB [ type="int" ]
A(k)
k = 0 .. NB
: c( k )
RW X <- c( k )
     -> c( k )
BODY [type=tpu]
X = np.asarray(X) * 2
print(X)
if X > 0:
    X = X - 1
END
"""


def test_golden_untraceable_body():
    jdf = ptg.compile_jdf(GOLDEN_UNTRACEABLE, name="golden").jdf
    fs = body_check.check_jdf_bodies(jdf)
    assert "BDY202" in codes(fs)
    # all three untraceable shapes are reported: np call, print, if-on-flow
    msgs = " | ".join(f.message for f in fs if f.code == "BDY202")
    assert "np.asarray" in msgs and "print()" in msgs and "if" in msgs


GOLDEN_NONDET = """
c [ type="collection" ]
NB [ type="int" ]
A(k)
k = 0 .. NB
: c( k )
RW X <- c( k )
     -> c( k )
BODY [type=tpu]
X = X * np.random.rand()
END
"""


def test_golden_nondeterministic_body():
    jdf = ptg.compile_jdf(GOLDEN_NONDET, name="golden").jdf
    fs = body_check.check_jdf_bodies(jdf)
    assert "BDY203" in codes(fs), fs


GOLDEN_ALIASED = """
c [ type="collection" ]
NB [ type="int" ]
A(k)
k = 0 .. NB
: c( k )
READ U <- c( k, k )
RW   X <- c( k, k )
       -> c( k, k )
BODY [type=tpu]
X = X + U
END
"""


def test_golden_aliased_tiles():
    jdf = ptg.compile_jdf(GOLDEN_ALIASED, name="golden").jdf
    fs = body_check.check_jdf_bodies(jdf)
    assert "BDY204" in codes(fs), fs
    assert any("donation" in f.message for f in fs if f.code == "BDY204")


GOLDEN_MISSING_WRITE = """
c [ type="collection" ]
NB [ type="int" ]
A(k)
k = 0 .. NB
: c( k )
RW X <- c( k )
     -> c( k )
BODY [type=tpu]
Y = X * 2
END
"""


def test_golden_missing_write():
    jdf = ptg.compile_jdf(GOLDEN_MISSING_WRITE, name="golden").jdf
    fs = body_check.check_jdf_bodies(jdf)
    assert "BDY205" in codes(fs), fs


GOLDEN_UNSHARED = """
%(prologue)s
c [ type="collection" ]
NB [ type="int" ]
A(k)
k = 0 .. NB
: c( k )
RW X <- c( k )
     -> c( k )
BODY [type=tpu]
%(body)s
END
"""


@pytest.mark.parametrize("body,prologue,why", [
    ("X = helper(X)", 'extern "C" %{\ndef helper(x):\n    return x\n%}',
     "'helper', which the prologue defines"),
    ("X = X * SCALE", 'extern "C" %{\nSCALE = [2.0]\n%}',
     "'SCALE', which the prologue defines"),
    ("X = sqrt(X)", 'extern "C" %{\nfrom jax.numpy import sqrt\n%}',
     "'sqrt', which the prologue defines"),
    ("X = X + c.mb", "", "the collection 'c'"),
    ("X = eval('X')", "", "eval/exec/globals"),
    ("import jax\nX = jax.numpy.abs(X)", "", "an import of its own"),
    # what a token can name: scalars, modules by attribute, task locals
    ("X = jnp.abs(X) * NB + k", "", None),
    ("X = m.sqrt(2.0) * X", 'extern "C" %{\nimport math as m\n%}', None),
    ("helper = X\nX = helper + 1", "", None),
])
def test_golden_unshared_programs(body, prologue, why):
    jdf = ptg.compile_jdf(GOLDEN_UNSHARED % {"body": body,
                                             "prologue": prologue},
                          name="golden").jdf
    found = [f for f in body_check.check_jdf_bodies(jdf)
             if f.code == "BDY206"]
    if why is None:
        assert not found, found
    else:
        f, = found
        assert why in f.message and "every taskpool" in f.message
        assert f.severity == "warn"


def test_check_function_dtd():
    def bad_kernel(a, b):
        import time
        if a > 0:           # traced-value branch
            a = a - b
        return a * time.time()

    fs = body_check.check_function(bad_kernel)
    assert "BDY202" in codes(fs) and "BDY203" in codes(fs)

    def good_kernel(a, b):
        return a @ b

    assert body_check.check_function(good_kernel) == []


def test_at_least_five_distinct_codes_catchable():
    """Acceptance: the golden set exercises >= 5 distinct finding codes."""
    seen = set()
    for spec in (GOLDEN_DANGLING, GOLDEN_NONRECIPROCAL,
                 GOLDEN_UNUSED_LOCAL, GOLDEN_WRITE_FEEDS_WRITE,
                 GOLDEN_ARITY, GOLDEN_UNSAT_GUARD,
                 GOLDEN_CTL_DATA_MISMATCH, GOLDEN_UNUSED_GLOBAL):
        seen |= codes(verify(spec))
    for spec in (GOLDEN_THIS_TASK, GOLDEN_NONDET, GOLDEN_ALIASED):
        jdf = ptg.compile_jdf(spec, name="golden").jdf
        seen |= codes(body_check.check_jdf_bodies(jdf))
    seen |= codes(ptg_check.verify_jdf_text(GOLDEN_CTL_CYCLE,
                                            name="golden", cycles=True))
    assert len(seen) >= 5, seen


# --------------------------------------------------------------------- #
# zero false positives over everything we ship                           #
# --------------------------------------------------------------------- #
def test_shipped_specs_are_clean():
    from tools import parsec_lint
    findings = []
    for path in parsec_lint.default_spec_files():
        findings.extend(parsec_lint.lint_spec_file(path, cycles=False))
    assert gate(findings) == [], [str(f) for f in gate(findings)]


def test_shipped_specs_enumerate_acyclic():
    """The cycle pass instantiates every shipped spec without a PTG109
    (and without an enumeration-failed note)."""
    from tools import parsec_lint
    findings = []
    for path in parsec_lint.default_spec_files():
        findings.extend(parsec_lint.lint_spec_file(path, cycles=True))
    assert not [f for f in findings if f.code in ("PTG109", "PTG180")], \
        [str(f) for f in findings]


def test_runtime_source_lock_lint_clean():
    fs = lock_check.lint_tree(os.path.join(ROOT, "parsec_tpu"))
    assert fs == [], [str(f) for f in fs]


@pytest.mark.slow
def test_self_lint_gate():
    """The tier-1 self-lint gate: tools/parsec_lint.py --strict over the
    repo's own specs, examples, and source exits 0.  Marked slow (a
    subprocess duplicate of the in-process gate tests) so a quick run
    can drop it with -m 'not slow'."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "tools", "parsec_lint.py"),
         "--strict"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, proc.stdout + proc.stderr


# --------------------------------------------------------------------- #
# the concurrency lint itself                                           #
# --------------------------------------------------------------------- #
LOCK_SRC = '''
import threading, time

_GUARDED_BY = {"Box._items": "_lock", "Peer.q": "cond"}

class Box:
    def __init__(self):
        self._items = []
        self._lock = threading.Lock()

    def good(self):
        with self._lock:
            return len(self._items)

    def bad(self):
        return len(self._items)

    def bad_block(self, sock):
        with self._lock:
            time.sleep(0.1)
            sock.sendall(b"x")

    def mgr(self):
        if not self._lock.acquire(blocking=False):
            return
        try:
            self._items.append(2)
        finally:
            self._lock.release()

    def helper(self):  # holds: self._lock
        self._items.pop()

    def waived(self):
        return self._items[:]            # lock: benign snapshot

class Peer:
    def touch(self, p):
        p.q.append(1)
        with p.cond:
            p.q.append(2)
            p.cond.wait(0.1)
'''


def test_lock_lint_catches_and_respects_annotations():
    fs = lock_check.lint_source(LOCK_SRC, "synthetic.py")
    by_line = {int(f.where.rsplit(":", 1)[1]): f.code for f in fs}
    # the three violations, and only those
    assert sorted(by_line.items()) == [
        (16, "LCK301"),   # Box.bad: unguarded read
        (20, "LCK302"),   # sleep while holding _lock
        (21, "LCK302"),   # sendall while holding _lock
        (39, "LCK301"),   # Peer.touch: p.q before taking p.cond
    ]


def test_lock_lint_ignores_unregistered_modules():
    assert lock_check.lint_source("x = 1\n", "m.py") == []


LOCK_SRC_UNREGISTERED = '''
import threading

_GUARDED_BY = {}

class S:
    def setup(self):
        self._lock = threading.Lock()
        self._scratch = threading.Lock()   # lock: single-owner scratch
'''


def test_lock_lint_unregistered_lock():
    """LCK303: an EMPTY _GUARDED_BY map is a contract, not a no-op — a
    lock constructed in an opted-in module must be some field's guard
    (the runtime/scheduling.py convention); a trailing # lock: comment
    waives one construction."""
    fs = lock_check.lint_source(LOCK_SRC_UNREGISTERED, "synthetic.py")
    assert [f.code for f in fs] == ["LCK303"]
    assert "_lock" in fs[0].message and fs[0].where.endswith(":8")


# --------------------------------------------------------------------- #
# dagenum as an importable library (cycle-pass substrate)               #
# --------------------------------------------------------------------- #
def test_dagenum_enumerate_text():
    from tools import dagenum
    tp, order = dagenum.enumerate_text("""
c [ type="collection" ]
NB [ type="int" ]
T(k)
k = 0 .. NB-1
: c( k )
RW A <- (k == 0) ? c( k ) : A T( k-1 )
     -> (k < NB-1) ? A T( k+1 ) : c( k )
BODY
pass
END
""", {"NB": 5}, name="chain")
    assert len(order) == 5
    # topological: instance k's pred is instance k-1
    keys = [inst.key for inst in order]
    assert keys == sorted(keys, key=lambda k: k[1])
    assert order[-1].preds == [("T", (3,))]


def test_dagenum_cycle_raises():
    from parsec_tpu.dsl.ptg.capture import CaptureError
    from tools import dagenum
    with pytest.raises(CaptureError, match="cycle"):
        dagenum.enumerate_text(GOLDEN_CTL_CYCLE, {}, name="cycle")


# --------------------------------------------------------------------- #
# diagnostics: Expr origins (file:line task.flow)                        #
# --------------------------------------------------------------------- #
def test_expr_origin_in_syntax_error():
    with pytest.raises(SyntaxError, match=r"myspec:6 A\.X"):
        ptg.compile_jdf("""
NB [ type="int" ]
A(k)
k = 0 .. NB
RW X <- NEW  [ shape=1 ]
     -> (k @@ 1) ? X A( k+1 )
BODY
pass
END
""", name="myspec")


def test_expr_origin_in_runtime_name_error():
    jdf = ptg.compile_jdf("""
NB [ type="int" ]
A(k)
k = 0 .. NB
RW X <- NEW  [ shape=1 ]
     -> (k < MISSING) ? X A( k+1 )
BODY
pass
END
""", name="myspec").jdf
    guard = jdf.task_classes[0].flows[0].deps[1].guard
    assert guard.origin == "myspec:6 A.X"
    with pytest.raises(NameError, match=r"myspec:6 A\.X"):
        guard({"k": 0})


def test_block_comment_preserves_line_numbers():
    """Multi-line /* */ comments must not shift diagnostic line numbers:
    the parser blanks them newline-preservingly so Expr.origin stays 1:1
    with the source text."""
    jdf = ptg.compile_jdf("""
NB [ type="int" ]
/* a
   multi-line
   comment */
A(k)
k = 0 .. NB
RW X <- NEW  [ shape=1 ]
     -> (k < MISSING) ? X A( k+1 )
BODY
pass
END
""", name="cmt").jdf
    guard = jdf.task_classes[0].flows[0].deps[1].guard
    assert guard.origin == "cmt:9 A.X"


def test_helper_name_error_keeps_traceback():
    """A NameError raised INSIDE a function the expression calls is not
    rewrapped with the JDF origin — the real traceback (pointing at the
    helper's buggy line) must survive."""
    import traceback
    from parsec_tpu.dsl.ptg.ast import Expr

    def helper(k):
        return undefined_thing  # noqa: F821

    e = Expr("helper(k)", origin="spec:6 A.X")
    with pytest.raises(NameError) as ei:
        e({"k": 0, "helper": helper})
    assert "spec:6" not in str(ei.value)
    frames = [t.name for t in traceback.extract_tb(ei.value.__traceback__)]
    assert "helper" in frames


def test_finding_str_format():
    f = Finding("PTG105", "msg", "spec:3 A.X")
    assert str(f) == "PTG105 [error] spec:3 A.X: msg"
    assert gate([f, Finding("PTG180", "m", severity="note")]) == [f]
