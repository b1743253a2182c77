"""Multi-host CLI worker: one process of a 2-process run launched through
the SHIPPED entry point (cli.main with --coordinator/--num_processes/
--process_id — the analogue of the reference's MASTER_ADDR/PORT +
init_process_group rendezvous, dbs.py:513-515).

Launched by tests/test_multihost.py as
``python _mh_cli_worker.py <proc_id> <num_procs> <port> <log_dir> <stat_dir>``.
Only the platform forcing (virtual CPU devices + gloo collectives) lives
here; the rendezvous itself is cli.main's job.
"""

import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"

import jax

jax.config.update("jax_cpu_collectives_implementation", "gloo")


def main() -> None:
    proc_id, num_procs, port, log_dir, stat_dir = sys.argv[1:6]
    from dynamic_load_balance_distributeddnn_tpu import cli

    rc = cli.main(
        [
            "-d", "true", "-ws", "4", "-b", "128",
            "-m", "mnistnet", "-ds", "mnist",
            "-e", "1", "--bucket", "8", "--n_train", "512",
            "--coordinator", f"localhost:{port}",
            "--num_processes", num_procs,
            "--process_id", proc_id,
            "--log_dir", log_dir,
            "--stat_dir", stat_dir,
        ]
    )
    print(f"CLI_RC {rc} nproc {jax.process_count()}", flush=True)


if __name__ == "__main__":
    main()
