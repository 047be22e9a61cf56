"""Device milliseconds of the served mapping program (`build_one`) per
scene it mapped in the window."""

from bench.readings import mapping_ms as read  # noqa: F401
