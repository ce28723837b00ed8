"""Engine entry and host to device: mean host-clock span around
``engine.run`` (cast, transfer, dispatch), in ms."""

from readers import mean_ms


def read(run):
    return mean_ms(run, "engine_call_s")
