"""Host clock round the eight ``Context.create_table`` calls, until every
array is on the device."""


def read(run):
    return run["setup"]["create_table_s"]
