from host_spans import read_idle_named as read  # noqa: F401
