"""Device: ``memory_stats()["peak_bytes_in_use"]`` right after the window,
on the fullest chip used."""


def read(obs):
    peak = obs.device.get("memory_peak_bytes")
    return peak / 1e9 if peak else None
