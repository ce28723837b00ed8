"""Device to host: mean host-clock span around ``np.asarray`` of each
call's output, taken after the output is ready on the device, in ms."""

from readers import mean_ms


def read(run):
    return mean_ms(run, "fetch_s")
