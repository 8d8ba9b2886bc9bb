from trace_reduce import idle_percent as read  # noqa: F401
