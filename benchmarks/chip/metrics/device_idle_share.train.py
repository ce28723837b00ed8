"""Device: share of the window in which no op ran (trace), in %."""

from readers import idle_share_pct as read  # noqa: F401
