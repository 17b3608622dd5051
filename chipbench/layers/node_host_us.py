"""Host time to build the DAG (stencil_dataflow() returns before the
device finishes) over its np * nt nodes: the harness's clock around the
call (host_clock; each DAG's build spans well over 250 ms at today's
cost a node). Layer: HPX model. Moves mcells_s."""


def read(trace, counters, ctx):
    return counters.get("node_host_us")
