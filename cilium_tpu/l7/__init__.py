"""L7 protocol matchers (the proxy verdict path).

In the reference, L7 matching runs in Envoy C++ filters
(envoy/cilium_l7policy.cc) / the Go Kafka proxy (pkg/proxy/kafka.go),
fed by NPDS policy (pkg/envoy/server.go getHTTPRule: Path/Method/Host
become Envoy regex HeaderMatchers — i.e. FULL-string matches).

Here the hot path is tensorized: HTTP rules compile to per-field
union DFAs with per-rule accept bitmasks (`regex_dfa`) and header
constraints to tables over interned header names and values, evaluated
by the device engine over padded request tensors (`http`); Kafka
rules compile to field-equality tables (`kafka`).  `fleet` compiles
every redirect's rules into one scoped matcher set and runs it as the
L7 stage of the persistent launch path.  Only requests over the
padded field budgets are re-decided on the host.

Generic parsers (`proxylib`) register themselves by name at import —
importing this package loads the bundled ones, as the reference's
proxylib init() hooks do.
"""

from cilium_tpu.l7 import memcached as _memcached  # noqa: F401
from cilium_tpu.l7 import testparsers as _testparsers  # noqa: F401
