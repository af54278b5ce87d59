"""Milliseconds per DEM round: the benchmark's span around each
``DEM.run`` over the rounds the run returned (host clock). It holds the
initialisation too, spread over the rounds."""


def read(layer):
    fits = layer.get("fits")
    if not fits or not all("rounds" in f for f in fits):
        return None
    rounds = sum(f["rounds"] for f in fits)
    return 1e3 * sum(f["t1"] - f["t0"] for f in fits) / rounds
