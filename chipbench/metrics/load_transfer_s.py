"""Seconds of set-up's eight ``Context.create_table`` calls inside their
``load_transfer`` spans: the engine's ``load_transfer_ms`` counter.  An engine
without the counter (before PR 31) has nothing to read."""


def read(run):
    from dask_sql_tpu.runtime import telemetry

    ms = telemetry.REGISTRY.get("load_transfer_ms")
    return None if ms is None else ms / 1e3
