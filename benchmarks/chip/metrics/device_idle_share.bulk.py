"""Device: share of the window in which no op ran, mean over the cell's
chips (trace), in %."""

from readers import idle_share_pct as read  # noqa: F401
