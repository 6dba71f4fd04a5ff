"""Router: how unevenly DDS placed the window's requests.  The busiest
replica's placements times the number of replicas over all placements
(1.0 is an even spread).  From the fleet's placement counter."""


def read(name, run):
    a, b = run.counters.get("window_start"), run.counters.get("window_end")
    if a is None or b is None:
        return None
    names = list(b["prefilled"])
    if len(names) < 2:
        return None
    placed = [b["placed"].get(n, 0) - a["placed"].get(n, 0) for n in names]
    total = sum(placed)
    return max(placed) * len(names) / total if total else None
