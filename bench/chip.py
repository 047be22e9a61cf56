"""The chip guard and the compile clock (copied from `chip_smoke.py`)."""

from __future__ import annotations


class NoChip(RuntimeError):
    """JAX's first device is not a TPU, or there are too few of them."""


def require_tpu(n_devices: int) -> list:
    """The first `n_devices` TPU devices; NoChip on any other host."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"no TPU: JAX's first device is {devs[0]} "
                     f"(platform {devs[0].platform!r})")
    if len(devs) < n_devices:
        raise NoChip(f"need {n_devices} TPU devices, JAX sees {len(devs)}")
    return devs[:n_devices]


class CompileClock:
    """Backend compiles (persistent-cache loads included): seconds and
    count, from JAX's monitoring events."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax
        self.seconds = 0.0
        self.count = 0

        def on_event(event, duration_secs, **_):
            if event == self.EVENT:
                self.seconds += duration_secs
                self.count += 1

        jax.monitoring.register_event_duration_secs_listener(on_event)

    def lap(self) -> tuple[float, int]:
        """(seconds, count) since the previous lap."""
        out = (self.seconds, self.count)
        self.seconds, self.count = 0.0, 0
        return out
