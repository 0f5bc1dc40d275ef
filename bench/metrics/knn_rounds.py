"""Engine: region rounds per kNN launch of the front end (each radius
round and the confirming one), from `AccessStats.knn_rounds`."""

from bench.stages import counter


def read(run):
    rounds = counter(run, "knn_rounds")
    launches = {t.t_launch for t in run.tickets if t.done and t.kind == "knn"}
    if rounds is None or not launches:
        return None
    return rounds / len(launches)
