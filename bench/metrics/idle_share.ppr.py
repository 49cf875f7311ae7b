"""Device idle share of the traced window: 1 - busy union / window."""

from bench.readers import idle_share as read  # noqa: F401
