"""Planner seconds of the cold first request (``plan_ms`` of the program's
response), paid in set-up."""


def read(ctx):
    return ctx["setup"].get("plan_cold_s")
