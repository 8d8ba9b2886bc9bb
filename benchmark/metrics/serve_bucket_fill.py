from occupancy import read_fill as read  # noqa: F401
