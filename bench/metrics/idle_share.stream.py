"""Percent of the traced window in which no operation ran on the chips
(1 - busy / window, busy being the union of op intervals, averaged over
the chips)."""

from bench.readings import idle_share as read  # noqa: F401
