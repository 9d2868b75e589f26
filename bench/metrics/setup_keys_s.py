"""Seconds the process spent building Paillier keys and fixed-base noise
tables, as the program counts them (`repro.obs`, ``keys_s``): set-up's
share of `setup_s` that grows with the key width on the host.  None
where the program keeps no such counter."""


def read(ctx):
    try:
        from repro import obs
    except ImportError:
        return None
    return obs.snapshot().get("keys_s")
