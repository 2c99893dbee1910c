/* SHOC 1.1.5 (Danalis et al., GPGPU-3 2010), src/opencl/level1/md/md.cl, kernel
 * `compute_lj_force`, written out from memory (the file is not in the container:
 * shoc_md.json, "assumed").  One work-item an atom: its position is ONE float4 load,
 * every neighbour's position ONE float4 gather through the neighbour list (laid out
 * neighList[j * inum + idx]: the work-items of a pass read neighbouring entries), the
 * Lennard-Jones force of the pairs inside the cutoff summed in a float4, ONE float4
 * store.  The only edits: SHOC's macros FPTYPE / posVecType / forceVecType written
 * out as float / float4 / float4. */
__kernel void compute_lj_force(__global float4 *force3, __global float4 *position,
                               const int neighCount, __global int *neighList,
                               const float cutsq, const float lj1, const float lj2,
                               const int inum)
{
    uint idx = get_global_id(0);
    float4 ipos = position[idx];
    float4 f = {0.0f, 0.0f, 0.0f, 0.0f};
    int j = 0;
    while (j < neighCount) {
        int jidx = neighList[j * inum + idx];
        float4 jpos = position[jidx];            // "uncoalesced read" in the source
        float delx = ipos.x - jpos.x;
        float dely = ipos.y - jpos.y;
        float delz = ipos.z - jpos.z;
        float r2inv = delx * delx + dely * dely + delz * delz;
        if (r2inv < cutsq) {
            r2inv = 1.0f / r2inv;
            float r6inv = r2inv * r2inv * r2inv;
            float force = r2inv * r6inv * (lj1 * r6inv - lj2);
            f.x += delx * force;  f.y += dely * force;  f.z += delz * force;
        }
        j++;
    }
    force3[idx] = f;
}
