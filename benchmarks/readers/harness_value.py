"""A number the harness itself took during the window (the pacer's lateness).

source: {"reader": "harness_value", "key": name}
"""


def read(source: dict, ctx: dict):
    return ctx["window"].get("harness", {}).get(source["key"])
