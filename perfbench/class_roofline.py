"""One task class's share of its roofline: what ``tile_kernels_roofline``
gives for the whole DAG, for the programs of one class.

count x the least time of one task (``roofline.least_time`` over the
class's file under ``kernels/``: max(flops / peak, bytes / bandwidth),
useful work only) over the device seconds the trace books under the
class's programs (``spans.class_device_seconds``), per factorization and
per chip.  Masked or padded work a program computes is in the
denominator and not in the numerator, so it lowers the share.

``obs`` does not say which cell it is of, so the cell is read where the
harness read it: ``--workload`` (and ``--rehearse``) of the command
line.  Anything missing -- no such option, no trace, no program of the
class in the trace, a device kind without peaks -- reads as nothing.
"""
import sys

from perfbench import roofline, spans, spec


def _option(name):
    argv = sys.argv
    for i, a in enumerate(argv):
        if a == name and i + 1 < len(argv):
            return argv[i + 1]
        if a.startswith(name + "="):
            return a.split("=", 1)[1]
    return None


def _cell():
    name = _option("--workload")
    if not name:
        return None
    try:
        cell = spec.Cell(spec.load_benchmark(), name)
        small = _option("--rehearse")
        if small:
            n, nb = (int(x) for x in small.split(","))
            cell.resize(N=n, NB=nb)
    except (spec.SpecError, ValueError):
        return None
    return cell


def read(obs, cls):
    busy = spans.class_device_seconds(obs, cls)
    cell = _cell()
    if not busy or cell is None:
        return None
    kernel = next((k for k in cell.kernels if k["class"] == cls), None)
    if kernel is None:
        return None
    try:
        import jax
        peaks = spec.peaks_of(jax.local_devices()[0].device_kind)
    except (ImportError, spec.SpecError):
        return None
    least, _rows = roofline.least_time([kernel], cell.sizes, peaks)
    return 100.0 * least / obs["chips"] / busy
