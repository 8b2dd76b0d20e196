#!/bin/sh
# Bring up the 5-node + control cluster (reference docker/up.sh): generate
# a one-off ssh keypair into ./secret and compose the containers.
set -e

INFO() { printf '[INFO] %s\n' "$*"; }

cd "$(dirname "$0")"

if [ ! -f ./secret/node.env ]; then
    INFO "Generating key pair"
    mkdir -p secret
    ssh-keygen -t rsa -N "" -f ./secret/id_rsa

    INFO "Generating ./secret/control.env"
    {
        printf 'SSH_PRIVATE_KEY='
        sed 's/$/↩/' ./secret/id_rsa | tr -d '\n'
        printf '\nSSH_PUBLIC_KEY='
        cat ./secret/id_rsa.pub
    } > ./secret/control.env

    INFO "Generating ./secret/node.env"
    printf 'ROOT_PUBLIC_KEY=' > ./secret/node.env
    cat ./secret/id_rsa.pub >> ./secret/node.env
fi

# The control image needs the framework source in its build context.
rm -rf control/jepsen_tpu control/tests
cp -r ../jepsen_tpu ../tests control/ 2>/dev/null || true

exec docker compose up --build "$@"
