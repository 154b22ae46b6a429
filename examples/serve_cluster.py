"""Serve the reduced qwen2-0.5b with batched requests on a real-compute cluster.

End-to-end data-plane demo: the LP plans the mixed/solo split, the
occupancy gate admits prefills, chunked prefill runs fused with decodes
(the paper's mixed iteration) as actual jitted compute, and completed
prefills migrate their KV to solo servers.

Run:  PYTHONPATH=src python examples/serve_cluster.py [--servers 4]
(the published widths: ``python -m repro.launch.serve``)
"""

import sys

from repro.launch.serve import main

if __name__ == "__main__":
    main(["--reduced", "--batch-cap", "4", "--chunk", "32", "--max-len",
          "256", *sys.argv[1:]])
