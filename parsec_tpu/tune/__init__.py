"""tune — closed-loop self-tuning (ISSUE 17).

The live health monitor (obs/live.py, ISSUE 16) *watches* the knobs
this package *turns*: a per-rank :class:`Controller` subscribes to the
monitor's window ticks and adapts three knob families at runtime —

- per-link quantized wire codec (lossless -> qbf16 -> qint8) within the
  ``tune_residual_budget``, escalating on bandwidth-bound links and
  de-escalating when compression shows no win, renegotiated live over
  the K_TUNE control frame toward "tn"-capable peers;
- device pipeline shape (``batch_max`` / ``prefetch_depth``),
  hill-climbed per device from batch occupancy and prefetch hit
  rate, with hysteresis and
  revert-on-regress against a us/task dispatch objective;
- stage-compile exclusion: a class whose compiled stage keeps firing
  the straggler detector is fed to ``stage_compile_exclude`` so the
  next taskpool over the same spec replans around it.

Everything lives behind the ``tune_auto`` MCA param: unset constructs
no controller, starts no subscription, and is bit-for-bit inert on the
wire (tests/test_tune_controller.py holds it:
``test_tune_auto_unset_constructs_no_controller`` and, on the wire's
bytes, ``test_wire_capture_tune_bit_identity``).
Every adaptation emits a ``tune:*`` instant annotation on the health
trace stream plus the ``PARSEC::TUNE::*`` gauges.
"""
from .controller import (CODEC_COST, CODEC_LADDER, Controller,
                         register_tune_gauges)

__all__ = ["Controller", "CODEC_LADDER", "CODEC_COST",
           "register_tune_gauges"]
