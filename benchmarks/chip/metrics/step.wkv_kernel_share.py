"""Share of the device time of the leaf operations under the ``wkv`` scope
(as ``step.wkv_ms`` counts it) spent in Pallas kernels, in %: operations
whose instruction is named for the kernel that the ``op_name`` path's
``<kernel>/pallas_call`` ends in (``wkv6_fwd.3`` under
``.../wkv/.../wkv6_fwd/pallas_call``), all chips together; 0 where the
scope holds no kernel, None where no operation has the scope."""
import re

import spans
import yardstick

WKV = re.compile(r"(^|[/(])wkv($|[/)])")
KERNEL = re.compile(r"(^|/)([^/]+)/pallas_call$")


def is_kernel(name, path):
    m = KERNEL.search(path)
    return bool(m) and re.sub(r"\.\d+$", "", name) == m.group(2)


def read(run):
    pt = spans.for_run(run)
    if pt is None or not run.traced_steps:
        return None
    total = kernels = 0
    for ops in run.trace.device_ops.values():
        for name, _, d in yardstick.clip(ops, run.trace_window):
            path = pt.op_scopes.get(name)
            if not path or not WKV.search(path) or \
                    yardstick.CONTAINER.match(name):
                continue
            total += d
            if is_kernel(name, path):
                kernels += d
    return 100.0 * kernels / total if total else None
