from occupancy import read_starved as read  # noqa: F401
