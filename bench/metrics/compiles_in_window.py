"""Compilations JAX made inside the window (backend compiles and programs
read back from the persistent cache).  Set-up warms every shape, so
this reads 0."""


def read(record):
    return record.compiles
