"""``forward_host_ms.study``, read in the service cells."""

import registry

read = registry.module("metrics", "forward_host_ms.study").read
