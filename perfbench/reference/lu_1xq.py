"""Reference for LU with partial pivoting where the program lays the
block columns out 1 x Q over its accelerators: ``reference/lu.py``'s
input, expected value and number, and nothing else of its own but a gate.

The configuration that names this reference (through its operation file)
guarantees that every writer of block column n runs on accelerator
n mod Q.  That is the program's to give (``ops/dgetrf_1d.py:
layout_1xq``); nothing in a data file can ask for it.  A checkout
without it runs the same call with its columns placed by load: another
deployment, and on four chips one whose windows hold a program built
in them.  So it is refused here, when the harness imports the
reference, before any set-up: exit code 2, no result line.
"""
import importlib

from perfbench import spec

from .lu import L_MAX, expected, make_input, plain_factor, residual  # noqa: F401

if not hasattr(importlib.import_module("parsec_tpu.ops.dgetrf_1d"),
               "layout_1xq"):
    raise spec.SpecError(
        "this checkout's ops.dgetrf_1d has no 1 x Q layout (layout_1xq): "
        "it places block columns by load, which is not the deployment "
        "the configuration states")
