"""Transient-device-error retry for host→device placement.

A device runtime can fail a ``device_put`` with ``UNAVAILABLE`` even
though the chip recovers seconds later. For a GAME
coordinate build that places dozens of bucket blocks over many minutes,
one transient placement failure otherwise kills the whole training
worker (observed: bench config 5 lost two 40-minute TPU attempts to a
single mid-build UNAVAILABLE). The reference delegates exactly this
class of failure to Spark task retry (SURVEY §5.3,
spark/RDDLike.scala:26); this helper is the placement-granular TPU
analogue.

Since PR 10 this is a thin wrapper over the shared retry substrate
(util/retry.py — capped jittered exponential, ``retry.attempts``
telemetry, the transient-only classifier). Only errors whose message
matches a transient pattern are retried; everything else (shape errors,
OOM, ...) propagates immediately.
"""
from __future__ import annotations

from photon_tpu.util.retry import RetryPolicy, is_transient, retry_call


def put_with_retry(fn, *, attempts: int = 3, backoff_s: float = 20.0):
    """Run ``fn()`` (a placement thunk returning device array(s)),
    retrying transient device errors. Returns fn's result.

    ``backoff_s`` seeds the exponential schedule's base (the historical
    linear schedule's first wait), doubling per retry up to a 2-minute
    cap with ±10% jitter.
    """
    return retry_call(
        fn,
        policy=RetryPolicy(
            attempts=attempts, base_s=backoff_s, multiplier=2.0,
            cap_s=120.0, jitter=0.1,
        ),
        classify=is_transient,
        label="device_put",
    )
