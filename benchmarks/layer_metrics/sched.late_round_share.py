"""Share of the judged fused rounds that were LATE: consumed after more
than twice the mean wall of the last 16 clean rounds (telemetry/prof.py,
RoundProf.judge_round; a round is judged once 16 clean rounds have been
seen). Delta late.rounds / delta late.judged; 0.0 when none was judged."""


def read(sources):
    a = sources["before"]["prof"].get("late")
    b = sources["after"]["prof"].get("late")
    if a is None or b is None:
        return None
    judged = b["judged"] - a["judged"]
    if judged <= 0:
        return 0.0
    return (b["rounds"] - a["rounds"]) / judged * 100.0
