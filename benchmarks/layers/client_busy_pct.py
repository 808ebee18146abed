"""CPU seconds the load generator's processes used inside the window over
window x processes: near 100 the generator, not the server, sets the pace.
"""


def read(before, after, client, trace):
    cpu = client.get("generator_cpu_s")
    if not cpu:
        return None
    return 100.0 * sum(cpu) / (client["window_s"] * len(cpu))
