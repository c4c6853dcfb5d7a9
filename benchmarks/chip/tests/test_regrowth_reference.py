"""The plain re-growth reference agrees with the program on every node.

For csa:16 and booth:16 under seeded random weights, the program's
predictions on each partitioned route equal, node for node, the classes of
``references/sage_regrowth.py`` under the partition the program chose; at 4
hops, the model's depth, they also equal the full graph's.  Pallas runs
interpreted on the CPU, so the kernel backend takes the fewer cases.
"""
import itertools
import json

import jax
import numpy as np
import pytest

import checks
import harness
import traffic as traffic_mod
from references import sage_polarity, sage_regrowth

BENCH = harness.load_bench()
CONFIGS = {c["name"]: json.loads((harness.ROOT / c["file"]).read_text()) for c in BENCH["configs"]}
BITS = 16
ROUTES = {"streamed": {"mesh_devices": None}, "mesh1": {"mesh_devices": 1},
          "partitioned": {"streaming": False}}
CASES = ([("ref", f, k, h, r) for f, k, h, r in
          itertools.product(sorted(CONFIGS), (2, 4), (1, 2, 4), ROUTES)]
         + [("groot_fused", f, 4, h, r) for f, h, r in
            itertools.product(sorted(CONFIGS), (1, 2, 4), ("streamed", "partitioned"))])


@pytest.fixture(scope="module")
def params():
    """One weight tree per configuration, so each session reuses its executor."""
    return {f: sage_polarity.init_params(c["gnn"], jax.random.key(1)) for f, c in CONFIGS.items()}


@pytest.mark.parametrize("backend,family,k,hops,route", CASES)
def test_program_matches_plain_regrowth(params, backend, family, k, hops, route):
    config, params = CONFIGS[family], params[family]
    session = {"backend": backend, "num_partitions": k, "regrow": True, "regrow_hops": hops,
               **ROUTES[route]}
    traffic = traffic_mod.build(config, {"bits": BITS, "verify": False, "session": session})
    sess = harness.make_session(traffic, config, params, False)
    design = traffic.program_design()
    res = harness.request(sess, traffic, design)
    prep = sess.prepare(design, dataset=traffic.family, bits=BITS)
    sess.close()
    n = traffic.design["kind"].shape[0]
    assert res.routing.mode == ("partitioned" if route == "partitioned" else "streamed")
    part = checks.valid_partition(checks.partition_of_cores(prep.subgraphs, n), n,
                                  res.routing.k)
    assert part is not None and res.routing.k == k

    g, x = sage_polarity.graph(traffic.design), sage_polarity.features(traffic.design)
    want = sage_regrowth.logits(params, x, g, part, regrow=True, hops=hops).argmax(1)
    np.testing.assert_array_equal(res.predictions, want)
    if hops >= config["gnn"]["num_layers"]:
        np.testing.assert_array_equal(res.predictions, sage_polarity.logits(params, x, g).argmax(1))
