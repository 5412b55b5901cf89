# # Interactive servers in sandboxes (tunnels)
#
# Counterpart of 11_notebooks/jupyter_inside_modal.py — an interactive
# server (Jupyter there; a stdlib HTTP file server here, same mechanics)
# runs inside a sandbox and is published through an `mtpu.forward` tunnel
# (:9). The pattern: boot the process in the sandbox, wait for the port,
# hand the tunnel URL to the user.
#
# Run: tpurun run examples/11_notebooks/server_in_sandbox.py

import sys
import urllib.request

import modal_examples_tpu as mtpu
from modal_examples_tpu.web.gateway import wait_for_port

app = mtpu.App("example-server-in-sandbox")

PORT = 18777


@app.local_entrypoint()
def main():
    sb = mtpu.Sandbox.create(timeout=120)
    try:
        with sb.open("notebook.txt", "w") as f:
            f.write("pretend this is a notebook\n")
        proc = sb.exec(
            sys.executable, "-m", "http.server", str(PORT), "--bind", "127.0.0.1"
        )
        assert wait_for_port("127.0.0.1", PORT, timeout=20), "server never bound"
        with mtpu.forward(PORT) as tunnel:
            print(f"server published at {tunnel.url}")
            with urllib.request.urlopen(f"{tunnel.url}/notebook.txt", timeout=5) as r:
                content = r.read().decode()
        assert "pretend" in content
        print("fetched through the tunnel:", content.strip())
        proc.kill()
    finally:
        sb.cleanup()
