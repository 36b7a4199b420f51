"""device_idle_pct.upload: share of the traced window in which no operation
ran on the device, in % (``tracereduce.idle_pct``)."""
from bench.tracereduce import idle_pct as read  # noqa: F401
