"""Share of the selected clients' updates that were computed and not
aggregated, in %: per round min(N, RONI-rejected + late) of the N updates
(an update both rejected and late counts once where the sum would pass N,
and twice below it), over the window's rounds.  The fixed-shape round
trains every selected client whatever its verdict, so this share does not
move ``rounds_per_s``: it reads how much of the round's SGD buys no
aggregate."""


def read(run):
    return run.counters.get("fl_update_waste")
