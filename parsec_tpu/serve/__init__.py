"""Multi-tenant persistent serving over one shared Context (ISSUE 18).

Admission control, weighted-fair scheduling, per-tenant quotas and SLO
attribution in front of the untouched runtime.  Nothing here is
constructed unless a :class:`SessionServer` is — with the ``serve``
knob unset the runtime, schedulers and wire format are bit-for-bit
those of a pre-serve build (tests/test_serve.py holds it:
``test_serve_knob_unset_is_inert`` and, on the wire's bytes,
``test_wire_capture_serve_bit_identity``).
"""
from .client import ServeClient, ServeTimeout
from .fairness import TenantFairness
from .server import AdmissionError, SessionServer, Submission, Tenant

__all__ = ["AdmissionError", "ServeClient", "ServeTimeout",
           "SessionServer", "Submission", "Tenant", "TenantFairness"]
