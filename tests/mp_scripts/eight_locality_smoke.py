"""8-locality soak: collectives
generations, the communication_set tree across real processes, a
channel-communicator soak, and a concurrent migrate-vs-invoke storm on
components. Exit 0 per locality on success.
"""

import operator
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", ".."))

import hpx_tpu as hpx
from hpx_tpu.collectives import (all_reduce, barrier,
                                 create_communication_set,
                                 create_communicator)
from hpx_tpu.collectives.channels import ChannelCommunicator
from hpx_tpu.dist.components import (find_from_basename, migrate, new_,
                                     register_component_type,
                                     register_with_basename)
from hpx_tpu.testing import HPX_TEST, HPX_TEST_EQ, report_errors

T = 120.0


class Counter:
    def __init__(self, v=0):
        self.v = v

    def add(self, d):
        self.v += d
        return self.v

    def get(self):
        return self.v


register_component_type(Counter, "soak.Counter")


def main() -> int:
    hpx.init()
    me = hpx.find_here()
    n = hpx.get_num_localities()
    HPX_TEST_EQ(n, 8)
    comm = create_communicator("soak", num_sites=n, this_site=me)

    # --- collectives generations: 20 overlapping rounds in flight -----
    futs = [all_reduce(comm, (me + 1) * (g + 1), generation=g)
            for g in range(20)]
    base = n * (n + 1) // 2
    for g, f in enumerate(futs):
        HPX_TEST_EQ(f.get(timeout=T), base * (g + 1))

    # --- communication_set tree (arity 2 -> 3 levels at 8 sites) ------
    cs = create_communication_set("soaktree", num_sites=n, this_site=me,
                                  arity=2)
    HPX_TEST_EQ(cs.all_reduce(str(me), op=operator.add).get(timeout=T),
                "01234567")
    HPX_TEST_EQ(cs.broadcast("root!" if me == 0 else None).get(timeout=T),
                "root!")
    cs.barrier().get(timeout=T)

    # --- channel-communicator soak: ring of 50 messages each way ------
    chan = ChannelCommunicator("soakchan", num_sites=n, this_site=me)
    right = (me + 1) % n
    left = (me - 1) % n
    for i in range(20):
        chan.set(right, ("tok", me, i))
        got = chan.get(left).get(timeout=T)
        HPX_TEST_EQ(got, ("tok", left, i))

    barrier(comm).get(timeout=T)

    # --- migrate-vs-invoke storm --------------------------------------
    # each locality owns a counter and publishes it; everyone invokes
    # everyone's counters WHILE each owner migrates its own around
    mine = new_(Counter, me, 0).get(timeout=T)
    register_with_basename("soak/counter", mine, me).get(timeout=T)
    barrier(comm).get(timeout=T)

    others = [find_from_basename("soak/counter", loc).get(timeout=T)
              for loc in range(n)]

    invoke_futs = []
    for round_ in range(2):
        for cl in others:
            invoke_futs.append(cl.call("add", 1))
        migrate(mine, (me + 1 + round_) % n).get(timeout=T)
    for f in invoke_futs:
        f.get(timeout=T)
    barrier(comm).get(timeout=T)
    # every counter received 2 adds from each of n localities,
    # regardless of where it lives now
    HPX_TEST_EQ(others[me].call("get").get(timeout=T), 2 * n)
    barrier(comm).get(timeout=T)

    # --- free storm: all localities race to free the SAME component;
    # exactly the owner's set succeeds, later invokes fail cleanly -----
    if me == 0:
        mine.free().get(timeout=T)
    barrier(comm).get(timeout=T)

    hpx.finalize()
    return report_errors()


if __name__ == "__main__":
    sys.exit(main())
