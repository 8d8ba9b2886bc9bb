from occupancy import read_pending as read  # noqa: F401
