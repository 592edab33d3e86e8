#!/usr/bin/env python3
"""tests/seconds.json from a junit file of the tier-1 run: the seconds each
file of ``tests/`` took (set-up, call and tear-down of its cases, summed),
which ``tests/conftest.py`` sorts the run by and ``tests/test_lint.py``
holds under a ceiling.

    python tools/test_seconds.py /tmp/_t1.xml      # the driver's command
                                                   # (/root/TESTS_LAST_RUN.json)
                                                   # writes that file
"""
import collections
import json
import os
import re
import sys
import xml.etree.ElementTree as ET

TABLE = os.path.join(os.path.dirname(os.path.abspath(__file__)), "..",
                     "tests", "seconds.json")


def seconds_by_file(junit_path: str) -> dict[str, int]:
    seconds = collections.Counter()
    for case in ET.parse(junit_path).iter("testcase"):
        module = re.match(r"tests\.(test_\w+)", case.get("classname", ""))
        if module:
            seconds[module.group(1) + ".py"] += float(case.get("time", 0))
    return {name: round(s) for name, s in sorted(
        seconds.items(), key=lambda kv: (-kv[1], kv[0])) if round(s) > 0}


if __name__ == "__main__":
    with open(TABLE, "w") as f:
        json.dump(seconds_by_file(sys.argv[1]), f, indent=0)
        f.write("\n")
