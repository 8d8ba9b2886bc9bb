"""How unevenly the router loads the experts held: the fullest
expert's (token, expert) pairs over the mean expert's, a row and expert
layer, mean over the window's rows (the program's counter of that
name, ``TPUModel.histograms()``). 1 is even; the grouped product waits
for nobody on one chip, so this is the all-to-all's and the stragglers'
number once experts lie across chips."""


def read(ctx):
    return ctx["counters"].get("moe_load_max_over_mean")
