"""Device memory in use once weights, ctx region and prefix pool are
placed (fullest chip), from the `engine up:` line. Nothing on the CPU."""


def read(sources):
    up = sources["engine_up"]
    if up["platform"] == "cpu":
        return None
    return max(up["hbm_gb"])
