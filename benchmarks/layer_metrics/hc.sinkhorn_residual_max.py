"""How far from doubly stochastic the hyper-connection's H_res stood when
its Sinkhorn iterations stopped: the program's counter
``dynamo_hc_sinkhorn_residual`` holds, for each consumed decode round,
the max over the round's tokens of |rowsum(H_res) - 1| in the last layer
(carried home in the round's token fetch); this is the mean of those
maxima over the window (a snapshot keeps a histogram's sum and count).
~1e-6 at the configuration's 20 iterations; a program that cuts
iterations reads 1e-2 and more. A program without the counter (no
hyper-connections): nothing to read."""

NAME = "dynamo_hc_sinkhorn_residual"


def read(sources):
    a = sources["before"]["histograms"].get(NAME)
    b = sources["after"]["histograms"].get(NAME)
    if a is None or b is None or b["count"] <= a["count"]:
        return None
    return (b["sum"] - a["sum"]) / (b["count"] - a["count"])
