"""``engine_host_ms.study``, read in the service cells."""

import registry

read = registry.module("metrics", "engine_host_ms.study").read
