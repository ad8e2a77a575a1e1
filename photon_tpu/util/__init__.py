"""Cross-cutting utilities (reference photon-lib/photon-client ``util/`` and
``event/`` packages): block timing, persistent job logging, lifecycle events
and date-partitioned input resolution. Profiler annotations are
``photon_tpu.obs.span``'s."""
from photon_tpu.util.dates import DateRange, DaysRange, resolve_date_range_paths
from photon_tpu.util.events import Event, EventEmitter, EventListener
from photon_tpu.util.io_utils import prepare_output_dir
from photon_tpu.util.logging import PhotonLogger
from photon_tpu.util.timed import Timed, timed

__all__ = [
    "DateRange",
    "DaysRange",
    "Event",
    "EventEmitter",
    "EventListener",
    "PhotonLogger",
    "Timed",
    "prepare_output_dir",
    "resolve_date_range_paths",
    "timed",
]
