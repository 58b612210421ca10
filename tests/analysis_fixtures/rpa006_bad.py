# broad-except violations; analyzed under repro/shard/router_fixture.py
def risky(work):
    try:
        work()
    except Exception:  # FIRE (broad)
        pass
    try:
        work()
    except (ValueError, Exception):  # FIRE (broad via tuple)
        pass
    try:
        work()
    except BaseException:  # FIRE
        pass
    try:
        work()
    except Exception:  # repro: ignore[RPA006]
        pass
